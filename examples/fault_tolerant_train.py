"""Fault-tolerant training demo: heartbeats, a simulated host failure,
elastic re-mesh, checkpoint restore, deterministic data replay.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/fault_tolerant_train.py

Phase 1 trains on a (4 data x 2 model) mesh with async checkpoints. At a
scripted step a "host" dies (we simulate the fleet losing 2 of 8 devices).
The monitor detects the failure, plan_remesh keeps TP=2 and shrinks data
4->3, and training resumes from the last committed checkpoint on the NEW
mesh — the elastic-restore path (same weights, different sharding) — with
the data pipeline replaying deterministically from the restored step.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.configs.base import get_config, reduced
from repro.data.pipeline import SyntheticCorpus
from repro.ft.monitor import HeartbeatMonitor, plan_remesh
from repro.launch.mesh import make_mesh
from repro.launch.train import build_trainer
from repro.train import loop as tl

CKPT = "/tmp/repro_ft_demo"
cfg = reduced(get_config("qwen1.5-0.5b"))
corpus = SyntheticCorpus(cfg.vocab_size, seed=11)
FAIL_AT = 6

print("== phase 1: (data=4, model=2) mesh ==")
mesh = make_mesh((4, 2), ("data", "model"))
jitted, shardings, _ = build_trainer(cfg, mesh, total_steps=20)
monitor = HeartbeatMonitor(num_hosts=4, timeout_s=5.0)
with mesh:
    state = jax.device_put(tl.init_train_state(jax.random.PRNGKey(0), cfg),
                           shardings)
    losses = []
    for step in range(20):
        if step == FAIL_AT:
            print(f"!! simulated failure of host 3 at step {step}")
            monitor.exclude([3])  # heartbeat timeout would do this for real
            break
        b = corpus.batch(step, 8, 32)
        state, m = jitted(state, {k: jnp.asarray(v) for k, v in b.items()})
        monitor.beat(0, step); monitor.beat(1, step); monitor.beat(2, step)
        monitor.beat(3, step)
        losses.append(float(m["loss"]))
        if (step + 1) % 3 == 0:
            ckpt.save(CKPT, step + 1, state)
            print(f"  step {step} loss {losses[-1]:.4f} [checkpoint]")

last = ckpt.latest_step(CKPT)
alive = len(monitor.alive()) * 2  # 2 devices per simulated host
plan = plan_remesh(alive, model=2)
print(f"\n== elastic re-mesh: {alive} devices alive -> "
      f"(data={plan.data}, model={plan.model}); resume from step {last} ==")

mesh2 = make_mesh((plan.data, plan.model), ("data", "model"))
jitted2, shardings2, _ = build_trainer(cfg, mesh2, total_steps=20)
with mesh2:
    template = tl.init_train_state(jax.random.PRNGKey(0), cfg)
    state2, start = ckpt.restore(CKPT, template, shardings=shardings2)
    for step in range(start, start + 6):
        b = corpus.batch(step, 6, 32)  # batch divisible by new data axis
        state2, m = jitted2(state2, {k: jnp.asarray(v) for k, v in b.items()})
        print(f"  step {step} loss {float(m['loss']):.4f} (on new mesh)")
print("\nOK: training continued across failure with deterministic replay.")
