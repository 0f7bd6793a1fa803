"""Serving engine: a typed request lifecycle over batched prefill/decode
with continuous batching.

``ServeEngine`` owns a fixed slot-batched KV cache (B slots x max_len) and
admits requests continuously: free slots are prefilled with new prompts
(left-aligned, their own position counters) while other slots keep decoding
— the standard continuous-batching discipline (vLLM-style, static slots
instead of paged blocks; pages are unnecessary when max_len is fixed per
deployment, and static layouts are what TPU SPMD wants).

The request lifecycle (this module's public surface):

* :class:`Request` carries a prompt plus :class:`SamplingParams`
  (temperature/top-k/top-p, per-request PRNG seed, stop tokens, output
  budget) and a ``priority`` for the scheduler.
* A pluggable :class:`~repro.serve.scheduler.Scheduler` owns the waiting
  queue; the engine asks it for admission waves whenever slots free up.
* :meth:`ServeEngine.generate` streams :class:`StreamEvent`s — one per
  emitted token, terminal events carrying the finish reason (``stop`` /
  ``length`` / ``cancelled``) and lifecycle stats (queue wait, TTFT,
  decode tok/s). :meth:`ServeEngine.cancel` evicts a live slot or a queued
  request mid-stream.
* :meth:`ServeEngine.run` remains as a thin closed-batch shim over
  ``generate`` (the benchmarks' token-parity baseline).

Hot-path discipline (the decode loop is the product):

* **One device->host transfer per step.** Sampling runs inside the jitted
  ``decode`` under PER-SLOT device vectors (temperature/top-k/top-p and a
  (slots, 2) batch of PRNG keys), so heterogeneous requests — greedy next
  to nucleus-sampled — batch in one compiled step; ``step()`` fetches a
  single (slots,) int32 vector. Each slot's key is its request's own
  (derived from the request seed, folded with the request-local token
  index), making batched streams bit-identical to running each request
  alone. An all-greedy batch drops to a PRNG-free argmax trace.
  ``sample_on_host=True`` restores the pre-overhaul per-slot host argmax —
  kept as the measured baseline for benchmarks/serve_bench.py.
  ``host_syncs`` counts every transfer either way.
* **Phase spans.** Each phase of a tick runs under a
  ``jax.profiler.TraceAnnotation`` (``serve.admit`` with its child
  ``serve.prefill_sync``, ``serve.decode_prep``, ``serve.decode_dispatch``,
  ``serve.decode_sync``, ``serve.commit``), one span per phase, never per
  slot; the compiled programs carry ``jax.named_scope`` names for the
  model's parts (``models/lm.py``). With no profiler recording, a span
  does next to nothing.
* **Donated cache buffers.** The jitted prefill/decode donate the cache
  operand (``donate_argnums``), so XLA writes the new cache in place
  instead of functionally copying ~cache_bytes every step;
  ``cache_bytes_moved`` counts any step where donation did NOT engage
  (asserted zero in benchmarks/serve_bench.py).
* **One compiled call per admission wave.** All free slots are admitted
  together: prompts are padded to one shared ``prompt_pad`` bucket and
  prefilled in a single jitted call that also ZEROES the admitted slots'
  cache/state (no separate reset pass) and samples each prompt's first
  token from its true last-real-token logits.
* **Bounded compile shapes for recurrent archs.** SSM/hybrid states
  integrate every fed token, so pad tokens would pollute them; instead of
  compiling one prefill per exact prompt length, prompts are fed in a
  power-of-two chunk ladder (``prompt_chunk``, then halves) with state
  threaded between calls — at most log2(prompt_chunk)+1 compiled shapes
  ever, regardless of traffic.

Resilience layer (every failure mode ends in a terminal StreamEvent with a
specific ``finish_reason`` — never a hang, a crash, or a corrupted
neighbor stream):

* **Deadlines.** ``Request.deadline_ms`` (submit -> done wall budget) and
  ``Request.decode_timeout_ms`` (first token -> done) are enforced in
  ``_tick`` against an injectable ``clock``: queued requests past deadline
  are shed at pop time, live slots finish with ``finish_reason="deadline"``
  before decoding another token.
* **Backpressure.** ``max_queue`` bounds the waiting queue. Overflow
  follows ``shed_policy``: ``"reject"`` turns the newcomer away
  (``submit_request`` returns False, terminal ``"rejected"`` event);
  ``"shed_lowest"`` drops the lowest-priority waiting request instead —
  unless the newcomer IS lowest, in which case it is rejected itself.
* **Numeric quarantine.** The jitted decode folds a per-slot finiteness
  check over the logits into the step and encodes failure as a ``-1``
  sentinel in the token vector — riding the step's single device->host
  transfer, so the 1 host sync/step discipline is preserved. A poisoned
  slot (inf/NaN logits — e.g. a degenerate KV scale plane) finishes with
  ``finish_reason="error"`` and its cache rows are re-zeroed; healthy
  slots' streams are bit-identical to a fault-free run (their rows pass
  through the check untouched; batch rows are independent).
* **Mid-flight preemption + swap/resume.** :meth:`preempt` extracts a
  live slot's cache rows (``_take_slots`` -> host copy) plus its stream
  state into a swap pool and requeues the request with the scheduler; on
  re-admission the rows are scattered back (``_put_slots``) and decoding
  continues bit-identically — no re-prefill. Schedulers may drive this via
  the optional ``should_preempt`` hook (PriorityScheduler evicts the
  lowest-priority live request when strictly higher-priority work waits).
* **Watchdog.** ``watchdog_timeout_s`` arms an ``ft.monitor``-based
  heartbeat over decode steps: a step whose wall gap exceeds the timeout
  is counted in ``stats()["stalled_steps"]`` (the training watchdog policy
  reused for serving).
* **Fault injection.** ``faults=`` accepts a ``serve/faults.py``
  :class:`FaultPlan`; the engine calls its ``before_decode`` hook each
  step, and adopts its deterministic clock when no explicit ``clock`` is
  given — every policy above is exercised by seeded, reproducible tests
  and ``launch/serve.py --chaos``.

Speculative decoding (``draft_params``/``draft_cfg``/``num_draft_tokens``):
the one-token decode tick generalizes to a **propose/verify/commit**
window. A cheap draft model (often a layer-sliced prefix of the target —
``serve/spec.py:draft_from_params``) decodes K candidates per slot from
its own dense KV cache; ONE batched ``lm.score_tokens`` pass runs the
target over all K+1 window positions (under ``kv_quant`` that is one fused
``prefill_attn_q8`` q-tile call against the rotated-int8 cache — dense or
paged); ``spec.verify_commit`` decides the accepted prefix + one
window-end token per slot on device. Every hot-path invariant survives
with "1 token/slot/step" generalized to "1..K+1 tokens/slot/window": ONE
device->host transfer moves the (S, K+1) token window + commit counts,
both caches donate in place, quarantine rides the same ``_POISONED``
sentinel, deadlines/cancel/preempt land at window boundaries, and paged
slots pre-extend their block chains by the window lookahead
(``paged.blocks_needed``). Greedy streams are bitwise identical to the
non-speculative engine; sampled streams follow Leviathan-style rejection
sampling under tagged per-request PRNG streams (``draft_tokens=0`` /
``draft=False`` slots stay bit-identical too — they ride the same window
machinery with kvec=0). SSM/hybrid targets are rejected: rolling back a
rejected window needs positional cache indexing, which recurrent state
lacks (ROADMAP leftover).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Iterable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import lm
from repro.models.layers import Runtime
from repro.serve.sampling import (
    FINISH_CANCELLED, FINISH_DEADLINE, FINISH_ERROR, FINISH_LENGTH,
    FINISH_REJECTED, FINISH_STOP, SamplingParams, StreamEvent,
)
from repro.serve.scheduler import Scheduler, get_scheduler

__all__ = ["Request", "ServeEngine", "SamplingParams", "StreamEvent"]

# In-band numeric-health sentinel: the jitted decode replaces a poisoned
# slot's sampled token with this (token ids are always >= 0), so quarantine
# detection rides the step's one device->host token transfer.
_POISONED = -1


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (L,) int32
    max_new: int = 32  # output budget (SamplingParams.max_new overrides)
    sampling: Optional[SamplingParams] = None  # None -> engine default
    priority: int = 0  # PriorityScheduler: higher admits first
    # --- SLO knobs (None disables; both measured on the engine clock) ---
    deadline_ms: Optional[float] = None  # submit -> done wall budget;
    #   queued requests past it are shed at pop time, live ones finish
    #   with finish_reason="deadline" before decoding another token
    decode_timeout_ms: Optional[float] = None  # first token -> done budget
    #   (covers time spent swapped out by preemption, by design: the SLO
    #   is the caller's wall clock, not the slot's)
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    finish_reason: Optional[str] = None
    preemptions: int = 0  # times this request was swapped out mid-flight
    # --- speculative-decoding accounting (filled by the engine) ---
    drafted: int = 0       # draft tokens proposed on this request's behalf
    accepted: int = 0      # of those, tokens the verifier committed
    spec_windows: int = 0  # propose/verify/commit windows executed
    # --- lifecycle stamps (perf_counter seconds, filled by the engine) ---
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None

    def stats(self) -> dict:
        """Lifecycle stats (present on the terminal StreamEvent)."""
        n = len(self.out)
        out: dict = {"tokens": n, "finish_reason": self.finish_reason}
        if self.t_submit is not None and self.t_admit is not None:
            out["queue_wait_s"] = self.t_admit - self.t_submit
        if self.t_submit is not None and self.t_first is not None:
            out["ttft_s"] = self.t_first - self.t_submit
        if self.t_first is not None and self.t_done is not None and n > 1:
            dt = self.t_done - self.t_first
            out["decode_tok_s"] = (n - 1) / dt if dt > 0 else float("inf")
        if self.preemptions:
            out["preemptions"] = self.preemptions
        if self.drafted:
            out["draft_proposed"] = self.drafted
            out["draft_accepted"] = self.accepted
            out["acceptance_rate"] = self.accepted / self.drafted
        return out


class ServeEngine:
    def __init__(self, params, cfg, *, slots: int = 4, max_len: int = 256,
                 rt: Optional[Runtime] = None, prompt_pad: int = 64,
                 prompt_chunk: int = 16, temperature: float = 0.0,
                 seed: int = 0, sample_on_host: bool = False,
                 cache_dtype=jnp.float32,
                 sampling: Optional[SamplingParams] = None,
                 scheduler: "str | Scheduler | None" = None,
                 eos_id: Optional[int] = None,
                 mesh=None, tp_shard_map: Optional[bool] = None,
                 clock=None, max_queue: Optional[int] = None,
                 shed_policy: str = "reject",
                 watchdog_timeout_s: Optional[float] = None,
                 faults=None, paged: bool = False,
                 num_blocks: Optional[int] = None, block_size: int = 16,
                 draft_params=None, draft_cfg=None,
                 draft_rt: Optional[Runtime] = None,
                 num_draft_tokens: int = 4):
        self.cfg = cfg
        self.rt = rt or Runtime(compute_dtype=jnp.float32)
        self.mesh = mesh
        if mesh is not None and mesh.shape.get("data", 1) > 1:
            # the serving layout head-shards the KV planes over "model" and
            # keeps the slot batch whole on every device — nothing below
            # partitions over "data", so a multi-way data axis would place
            # every "replicated" leaf wrong silently. Name the limitation
            # instead (ROADMAP: data-parallel serving is future work).
            raise ValueError(
                f"ServeEngine assumes a serving mesh with a trivial 'data' "
                f"axis (data=1); got data={mesh.shape['data']}. The slot "
                f"batch is not data-sharded — reshape the mesh so all "
                f"devices sit on the 'model' axis for tensor-parallel "
                f"serving.")
        if mesh is not None:
            # Tensor-parallel serving (serve/tp.py): derive the serving
            # Rules, place the packed planes column-sharded (and fp leaves
            # replicated) over the mesh, and thread rules/mesh into the
            # Runtime so shard_hint constraints steer GSPMD inside the
            # jitted prefill/decode. tp_shard_map defaults on for real TPU,
            # where GSPMD cannot partition a pallas_call and the kernels
            # must be shard_mapped explicitly.
            from repro.serve import tp as tp_mod  # lazy: optional subsystem
            rules = tp_mod.serve_rules(mesh, cfg)
            if tp_shard_map is None:
                tp_shard_map = jax.default_backend() == "tpu"
            self.rt = dataclasses.replace(self.rt, rules=rules, mesh=mesh,
                                          tp_shard_map=bool(tp_shard_map))
            params = tp_mod.shard_params(params, cfg, rules)
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.prompt_pad = prompt_pad
        self.prompt_chunk = prompt_chunk
        self.seed = int(seed)
        self.sample_on_host = sample_on_host
        # --- speculative decoding (propose/verify/commit; serve/spec.py) ---
        self.spec = draft_params is not None
        self.draft_cfg = draft_cfg
        if self.spec:
            if draft_cfg is None:
                raise ValueError("draft_params needs a draft_cfg")
            if sample_on_host:
                raise ValueError(
                    "sample_on_host is the measured pre-overhaul baseline; "
                    "speculative decoding needs on-device sampling (the "
                    "accept/commit decision rides the window's one token "
                    "transfer)")
            if num_draft_tokens < 1:
                raise ValueError(
                    f"num_draft_tokens must be >= 1, got {num_draft_tokens}")
            for c, role in ((cfg, "target"), (draft_cfg, "draft")):
                if c.family not in ("dense", "vlm", "moe"):
                    raise ValueError(
                        f"speculative decoding needs pure-attention "
                        f"families (dense/vlm/moe); the {role} is "
                        f"{c.family!r} — recurrent state cannot roll back "
                        f"a rejected window (positional cache indexing is "
                        f"what makes rejection free)")
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size}: acceptance compares distributions "
                    f"over the same token ids")
            self._spec_k = int(num_draft_tokens)
            drt = draft_rt or self.rt
            if mesh is not None:
                from repro.serve import tp as tp_mod
                drt = dataclasses.replace(
                    drt, tp_shard_map=self.rt.tp_shard_map)
                draft_params, drt = tp_mod.place_draft(
                    draft_params, draft_cfg, mesh, drt)
            self.draft_rt = drt
            self.draft_params = draft_params
        else:
            self._spec_k = 0
            self.draft_rt = None
            self.draft_params = None
        # engine-default sampling for requests that don't carry their own;
        # the legacy ``temperature`` knob folds into it (and stays live via
        # the ``temperature`` property below)
        self.default_sampling = sampling or SamplingParams(
            temperature=float(temperature))
        self.scheduler: Scheduler = get_scheduler(scheduler)
        self.eos_id = eos_id if eos_id is not None else getattr(
            cfg, "eos_token_id", None)
        # --- resilience layer (see module docstring) ---
        self.faults = faults
        if clock is None and faults is not None:
            clock = getattr(faults, "clock", None)  # deterministic test time
        self._clock = clock or time.perf_counter
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if shed_policy not in ("reject", "shed_lowest"):
            raise ValueError(
                f"shed_policy must be 'reject' or 'shed_lowest', "
                f"got {shed_policy!r}")
        self.max_queue = max_queue
        self.shed_policy = shed_policy
        # rid -> {"cache": host pytree, "pos": int, "next_tok": int} for
        # requests swapped out mid-flight by preempt()
        self._swapped: dict[int, dict] = {}
        self.watchdog = None
        if watchdog_timeout_s is not None:
            from repro.ft.monitor import HeartbeatMonitor  # lazy: ft layer
            self.watchdog = HeartbeatMonitor(
                1, timeout_s=float(watchdog_timeout_s), clock=self._clock)
        # --- resilience counters (surfaced via stats()) ---
        self.requests_rejected = 0  # backpressure: newcomer turned away
        self.requests_shed = 0      # backpressure: waiting victim dropped
        self.requests_invalid = 0   # malformed (empty prompt) at submit/admit
        self.deadline_expired = 0   # queued or live deadline/timeout expiries
        self.quarantined = 0        # slots evicted by the numeric-health check
        self.preemptions = 0        # live slots swapped out mid-flight
        self.resumes = 0            # swapped requests scattered back in
        self.stalled_steps = 0      # decode steps slower than the watchdog
        # --- paged-pool counters (zero for dense engines) ---
        self.blocks_swapped = 0     # blocks host-swapped by preemption
        self.pool_exhausted = 0     # slots error-finished on a dry pool
        self.max_concurrent = 0     # peak simultaneously-decoding requests
        # Runtime.kv_quant lays the attention cache out as rotated-int8
        # codes + fp16 scales (serve/kv_quant.py); cache_dtype is the fp
        # cache element type otherwise (f32 default keeps CPU tests exact,
        # bf16 is the deployment baseline the bytes ratio is quoted against)
        self.paged = bool(paged)
        if self.paged:
            # paged pool (serve/paged.py): cache positions come from a
            # shared ref-counted block pool instead of a per-slot max_len
            # reservation — admission is bounded by LIVE tokens, not slots
            from repro.serve import paged as paged_mod
            if not self.rt.kv_quant:
                raise ValueError(
                    "paged=True requires Runtime(kv_quant=True): the block "
                    "pool is laid out over the rotated-int8 codes + scale "
                    "planes")
            # +_spec_k: a speculative verify writes K+1 positions starting
            # at pos <= max_len - 2, so the address space must reach
            # max_len - 2 + K (zero when speculation is off — exact old
            # shapes, byte parity)
            n_pos = (max_len + self._spec_k
                     + (cfg.frontend_len if cfg.frontend else 0))
            self.block_size = int(block_size)
            # per-slot table width: enough entries to address every logical
            # position a slot can reach
            self._maxb = -(-n_pos // self.block_size)
            if num_blocks is None:
                # default: dense-equivalent capacity (every slot could run
                # to max_len) + the reserved null block — callers shrink it
                # to realize the memory win
                num_blocks = slots * self._maxb + 1
            self.num_blocks = int(num_blocks)
            self.pool = paged_mod.BlockPool(self.num_blocks, self.block_size)
            self._table = np.zeros((slots, self._maxb), np.int32)
            self._slot_blocks: list[list[int]] = [[] for _ in range(slots)]
            self.cache = paged_mod.init_paged_cache(
                cfg, self.num_blocks, self.block_size)
        else:
            self.block_size = None
            self.num_blocks = None
            self.pool = None
            # +_spec_k for the speculative write horizon (0 when off)
            self.cache = lm.init_cache(cfg, slots, max_len + self._spec_k,
                                       dtype=cache_dtype,
                                       kv_quant=self.rt.kv_quant)
        if mesh is not None:
            # per-device KV-cache shards from step 0: codes + scale planes
            # head-sharded over `model` (replicated when GQA doesn't divide)
            from repro.serve import tp as tp_mod
            self.cache = tp_mod.shard_cache(self.cache, cfg, self.rt.rules)
        if self.spec:
            # the draft's own KV cache: always dense slot-batched (the
            # draft is small by construction, so paging it buys nothing),
            # same +K horizon so a fully-accepted window's final proposal
            # is cached with no stale hole
            self.draft_cache = lm.init_cache(
                draft_cfg, slots, max_len + self._spec_k, dtype=cache_dtype,
                kv_quant=self.draft_rt.kv_quant)
            if mesh is not None:
                from repro.serve import tp as tp_mod
                self.draft_cache = tp_mod.shard_cache(
                    self.draft_cache, draft_cfg, self.draft_rt.rules)
        else:
            self.draft_cache = None
        self._cache_nbytes = self.cache_bytes  # fixed for the engine's life
        self._paths = self._resolved_paths()
        self.pos = np.zeros(slots, dtype=np.int32)  # next write index per slot
        self.active: list[Optional[Request]] = [None] * slots
        self._next_tok = np.zeros(slots, dtype=np.int32)
        # --- per-slot sampling state, packed to device vectors each step ---
        self._temp = np.zeros(slots, np.float32)
        self._top_k = np.zeros(slots, np.int32)
        self._top_p = np.ones(slots, np.float32)
        self._keys = np.zeros((slots, 2), np.uint32)
        self._slot_stop: list[frozenset[int]] = [frozenset()] * slots
        self._slot_max_new: list[int] = [0] * slots
        # per-slot speculative window size (0 = one-token decode; set at
        # install from SamplingParams.draft/draft_tokens, always 0 on
        # non-speculative engines)
        self._slot_draft_k = np.zeros(slots, np.int32)
        self._pending_events: list[StreamEvent] = []
        # --- perf counters (read by the bench harness: decode_steps and
        # stats(); by launch/serve.py and by tests) ---
        self.host_syncs = 0       # device->host transfers
        self.tokens_decoded = 0   # tokens emitted by step()
        self.decode_steps = 0     # jitted decode calls
        self.cache_bytes_moved = 0  # bytes functionally copied (donation off)
        self.cache_donated = False  # did the last decode donate in place?
        # --- speculative counters ---
        self.spec_steps = 0       # propose/verify/commit windows executed
        self.draft_proposed = 0   # draft tokens offered for verification
        self.draft_accepted = 0   # of those, tokens committed
        self._jit_prefill = jax.jit(self._prefill_impl,
                                    static_argnames=("plen", "fresh"),
                                    donate_argnums=(1,))
        self._jit_decode = jax.jit(self._decode_impl, donate_argnums=(1,))
        self._jit_decode_logits = jax.jit(self._decode_logits_impl,
                                          donate_argnums=(1,))
        if self.spec:
            self._jit_draft_prefill = jax.jit(self._draft_prefill_impl,
                                              donate_argnums=(1,))
            self._jit_propose = jax.jit(self._propose_impl,
                                        donate_argnums=(1,))
            self._jit_verify = jax.jit(self._verify_impl,
                                       donate_argnums=(1,))
            # a speculative engine prices SJF admission by expected slot
            # OCCUPANCY (prefill + decode STEPS), not prompt length alone:
            # a draft-enabled request frees its slot up to (K+1)x faster
            set_cost = getattr(self.scheduler, "set_cost", None)
            if set_cost is not None:
                set_cost(self._admission_cost)
        if self.rt.autotune:
            from repro.kernels import autotune as autotune_mod
            # no-op on CPU/interpret; on TPU, pre-tunes every QTensor matmul
            # shape at decode batch = slots so the hot loop runs tuned tiles
            autotune_mod.tune_params_shapes(params, slots)
            if self.spec and self.rt.kv_quant:
                # pre-tune the verify pass's NARROW q-width attention shape
                # (K+1 window positions over the full cache) so the first
                # speculative window already runs tuned tiles
                attn = self.cache.get("attn")
                if attn:
                    cl = (self._maxb * self.block_size if self.paged
                          else int(attn["k"].shape[3]))
                    kvh = cfg.num_kv_heads
                    autotune_mod.autotune_attn(
                        cl, cfg.resolved_head_dim, kvh, batch=slots,
                        g=max(1, getattr(cfg, "num_heads", kvh) // kvh),
                        q_width=self._spec_k + 1)

    def _resolved_paths(self) -> dict:
        """What the ``backend`` knob resolved to on this device: the
        quantized matmuls' implementation(s) and the quantized-cache
        attention's (``"pallas"``/``"ref"``), so a run can prove which
        code served it."""
        from repro.core.qlinear import resolve_backend
        from repro.core.quantize import QTensor
        from repro.kernels.attn_decode import resolve_attn_path

        backend = "pallas" if self.rt.use_kernel else self.rt.backend
        fmts = {leaf.meta.fmt for leaf in jax.tree.leaves(
            self.params, is_leaf=lambda x: isinstance(x, QTensor))
            if isinstance(leaf, QTensor)}
        mm = sorted({resolve_backend(backend, f, self.rt.quant_mode)
                     for f in fmts})
        attn = None
        if not self.cfg.attention_free:
            attn = (resolve_attn_path(self.rt.backend,
                                      self.cfg.resolved_head_dim)
                    if self.rt.kv_quant else "ref")
        return {"matmul_path": "+".join(mm) or "ref", "attn_path": attn}

    @property
    def temperature(self) -> float:
        """Legacy knob: the engine-default temperature. Reads/writes route
        through ``default_sampling`` so mutating it between batches still
        takes effect (already-admitted requests keep their resolved
        params)."""
        return self.default_sampling.temperature

    @temperature.setter
    def temperature(self, value: float) -> None:
        self.default_sampling = dataclasses.replace(
            self.default_sampling, temperature=float(value))

    @classmethod
    def from_checkpoint(cls, ckpt_dir: str, cfg, *, step: Optional[int] = None,
                        mesh=None, **kw) -> "ServeEngine":
        """Boot an engine from a bare checkpoint directory — including
        policy-quantized checkpoints, whose QTensor leaves are rebuilt from
        their packed planes without re-running Algorithm 1 (the
        serve-from-disk path of the deployment story).

        With ``mesh``, each leaf is ``device_put`` into its serving TP
        placement AS IT LOADS (restore-to-sharding): packed planes go
        straight to their column shards, so the full plane set never
        materializes on one device — the path that makes 235B-class plane
        sets bootable."""
        from repro.checkpoint import ckpt as ckpt_mod  # lazy: optional dep

        shardings = None
        if mesh is not None:
            from repro.serve import tp as tp_mod
            shardings = tp_mod.restore_shardings(cfg, mesh)
        params, _ = ckpt_mod.restore_params(ckpt_dir, step=step,
                                            shardings=shardings)
        return cls(params, cfg, mesh=mesh, **kw)

    # --- compiled kernels -------------------------------------------------
    def _prefill_impl(self, params, cache, tokens, slots, last_idx, pos0,
                      keys, temp, top_k, top_p, table=None, *, plen, fresh):
        """One admission wave: tokens (G, plen) for slot ids ``slots`` (G,).

        ``fresh=True`` starts each admitted slot from a ZEROED state (the
        old per-slot reset pass folded into this same compiled call);
        ``fresh=False`` continues from the slot's current state (the
        SSM/hybrid chunk ladder). ``keys`` is a (G, 2) batch of per-request
        PRNG keys (None for an all-greedy wave: no PRNG in the trace).

        PAGED engines pass ``table`` (G, MAXB) — the admitted slots' block
        rows. Writes scatter through the table into the shared pool, so
        there is no per-slot gather/zero/scatter: freshly allocated blocks
        may hold a finished request's stale FINITE codes, which the kv_len
        mask zeroes exactly (the finite-garbage invariant; serve/paged.py).
        Returns (cache, sampled (G,) first tokens, last-real-token logits
        (G, V))."""
        if table is not None:
            model_cache = {"attn": cache["attn"], "table": table}
            logits, new_cache, _ = lm.forward(
                params, tokens, self.rt, self.cfg, cache=model_cache,
                pos=pos0, last_idx=last_idx)
            cache = {"attn": new_cache["attn"]}
        else:
            g = tokens.shape[0]
            if fresh:
                slot_cache = _zero_slots_like(cache, g)
            else:
                slot_cache = _take_slots(cache, slots)
            # pad tokens run through the model (masked later via pos), but
            # the head + first sampled token come from the TRUE last prompt
            # position only — one V-row per slot, not V logits per pad
            logits, new_slot_cache, _ = lm.forward(
                params, tokens, self.rt, self.cfg, cache=slot_cache,
                pos=pos0, last_idx=last_idx)
            cache = _put_slots(cache, new_slot_cache, slots)
        last = logits[:, 0]
        tok = _sample_slots(last, keys, jnp.zeros_like(slots), temp,
                            top_k, top_p)
        return cache, tok, last

    def _model_cache(self, cache, table):
        """The cache pytree the model sees: the engine cache, plus the
        block table threaded OUTSIDE it for paged engines — the table rides
        the jitted calls as its own argument so the cache-donation probe
        (``jax.tree.leaves(self.cache)``) never sees it."""
        return cache if table is None else {"attn": cache["attn"],
                                            "table": table}

    def _decode_impl(self, params, cache, tokens, positions, keys, gen,
                     temp, top_k, top_p, table=None):
        """tokens (S, 1); per-slot positions (S,). Sampling stays on device
        under per-slot vectors: the step's only fetch is the (S,) token
        vector. ``gen`` (S,) is each request's own token index — folded
        into its key so row draws don't depend on slot or batchmates."""
        logits, new_cache = lm.decode_step(
            params, tokens, self._model_cache(cache, table), positions,
            self.rt, self.cfg)
        if table is not None:
            new_cache = {"attn": new_cache["attn"]}
        last = logits[:, 0]
        tok = _sample_slots(last, keys, gen, temp, top_k, top_p)
        # numeric-health check folded into the step: a slot whose logits
        # row went non-finite (inf/NaN — e.g. a poisoned KV scale plane)
        # reports the in-band _POISONED sentinel instead of a token, so
        # quarantine costs zero extra host syncs; healthy rows pass through
        # untouched (batch rows are independent -> bit-identical streams)
        with jax.named_scope("sample"):
            ok = lm.finite_rows(last)
            return jnp.where(ok, tok, _POISONED), new_cache

    def _decode_logits_impl(self, params, cache, tokens, positions,
                            table=None):
        """Pre-overhaul decode: ship logits out, sample on host."""
        logits, new_cache = lm.decode_step(
            params, tokens, self._model_cache(cache, table), positions,
            self.rt, self.cfg)
        if table is not None:
            new_cache = {"attn": new_cache["attn"]}
        return logits[:, 0], new_cache

    # --- speculative propose/verify (compiled) ----------------------------
    def _draft_prefill_impl(self, params, cache, tokens, slots, pos0):
        """Admission-wave prefill of the DRAFT cache: zero the admitted
        slots and append the padded prompt bucket. No head, no sampling —
        the target's prefill picks the first token; the draft only needs
        the KV state. Pad positions hold finite garbage behind the kv_len
        mask / under the window's sequential overwrites, exactly like the
        target's bucketed prefill."""
        g = tokens.shape[0]
        new_slot = lm.advance_cache(params, tokens,
                                    _zero_slots_like(cache, g), pos0,
                                    self.draft_rt, self.draft_cfg)
        return _put_slots(cache, new_slot, slots)

    def _propose_impl(self, dparams, dcache, tokens, positions, keys, gen,
                      temp, top_k, top_p):
        """K sequential draft steps + one final cache advance. Returns
        (cand (S, K+1) = [anchor, d_1..d_K], qlog (S, K, V) draft
        scaled+masked logits (None on an all-greedy trace), new draft
        cache). Proposal w is drawn from the slot's DRAFT_TAG PRNG stream
        at generation index gen + w — mirroring ``lm.sample_tokens``'s
        masked-categorical path exactly, so ``qlog`` IS the distribution
        the draw came from (what rejection sampling requires). The final
        ``advance_cache`` writes d_K at pos + K: a fully-accepted window
        leaves no stale hole for the next window to read."""
        from repro.serve import spec as spec_mod
        k = self._spec_k
        cand = [tokens[:, 0]]
        qlogs = []
        cur = tokens
        for w in range(k):
            logits, dcache = lm.decode_step(dparams, cur, dcache,
                                            positions + w, self.draft_rt,
                                            self.draft_cfg)
            last = logits[:, 0].astype(jnp.float32)
            if keys is None:  # all-greedy: argmax proposals, no PRNG
                d = jnp.argmax(last, axis=-1).astype(jnp.int32)
            else:
                scaled = last / jnp.maximum(temp, 1e-6)[:, None]
                if top_k is not None or top_p is not None:
                    scaled = lm.top_mask(scaled, top_k, top_p)
                dk = spec_mod.draft_keys(keys, gen, w)
                sampled = jax.vmap(
                    lambda kk, row: jax.random.categorical(kk, row)
                )(dk, scaled).astype(jnp.int32)
                d = jnp.where(temp > 0, sampled,
                              jnp.argmax(last, axis=-1).astype(jnp.int32))
                qlogs.append(scaled)
            cand.append(jnp.clip(d, 0, self.cfg.vocab_size - 1))
            cur = cand[-1][:, None]
        dcache = lm.advance_cache(dparams, cur, dcache, positions + k,
                                  self.draft_rt, self.draft_cfg)
        qlog = jnp.stack(qlogs, axis=1) if qlogs else None
        return jnp.stack(cand, axis=1), qlog, dcache

    def _verify_impl(self, params, cache, cand, positions, kvec, keys, gen,
                     temp, top_k, top_p, qlog, table=None):
        """One batched target pass over the K+1 window positions
        (``lm.score_tokens`` — under kv_quant a single fused
        ``prefill_attn_q8`` call per layer), then the on-device
        accept/commit decision. Numeric quarantine generalizes: a slot
        whose logits went non-finite at any position its window can USE
        (<= kvec; later rows read lookahead positions past the slot's
        paged allocation, which hold finite-but-meaningless null-block
        garbage) reports a fully _POISONED row with n=1, riding the same
        single transfer."""
        from repro.serve import spec as spec_mod
        logits, new_cache = lm.score_tokens(
            params, cand, self._model_cache(cache, table), positions,
            self.rt, self.cfg)
        if table is not None:
            new_cache = {"attn": new_cache["attn"]}
        out, n = spec_mod.verify_commit(logits, cand, kvec, keys=keys,
                                        gen=gen, temp=temp, top_k=top_k,
                                        top_p=top_p, qlog=qlog)
        used = jnp.arange(cand.shape[1])[None, :] <= kvec[:, None]
        ok = jnp.all(lm.finite_rows(logits) | ~used, axis=1)
        out = jnp.where(ok[:, None], out, _POISONED)
        n = jnp.where(ok, n, 1)
        return out, n, new_cache

    # --- request lifecycle ------------------------------------------------
    def _spec_k_for(self, req: Request) -> int:
        """This request's speculative window size: the engine's
        ``num_draft_tokens``, capped (never raised) by
        ``SamplingParams.draft_tokens``, zeroed by ``draft=False`` — and
        always 0 on a non-speculative engine."""
        if not self.spec:
            return 0
        sp = req.sampling or self.default_sampling
        if sp.draft is False:
            return 0
        if sp.draft_tokens is not None:
            return max(0, min(int(sp.draft_tokens), self._spec_k))
        return self._spec_k

    def _admission_cost(self, req: Request) -> float:
        """SJF job-size estimate under speculation: prefill cost (prompt
        length) plus expected decode STEPS — the output budget amortized
        by the request's window size (a K-draft window commits up to K+1
        tokens per step)."""
        sp = req.sampling or self.default_sampling
        new = sp.max_new if sp.max_new is not None else req.max_new
        return float(len(req.prompt)) + float(new) / (
            1 + self._spec_k_for(req))

    def _resolve(self, req: Request) -> SamplingParams:
        sp = req.sampling or self.default_sampling
        over: dict = {}
        if sp.max_new is None:
            over["max_new"] = req.max_new
        if sp.greedy and (sp.top_k > 0 or sp.top_p < 1.0):
            # argmax ignores the filters by spec — normalize them to the
            # inert values so a greedy request never drags top_mask's
            # full-vocab sort into a mixed batch's decode trace
            over.update(top_k=0, top_p=1.0)
        return dataclasses.replace(sp, **over) if over else sp

    def _terminal(self, req: Request, reason: str) -> StreamEvent:
        """Stamp a request done OFF-slot (rejected / shed / expired while
        queued / invalid) and queue its terminal event for the next tick."""
        if req.t_submit is None:
            req.t_submit = self._clock()
        req.done = True
        req.finish_reason = reason
        req.t_done = self._clock()
        ev = StreamEvent(req.rid, None, len(req.out), finished=True,
                         finish_reason=reason, stats=req.stats())
        self._pending_events.append(ev)
        return ev

    def submit_request(self, req: Request) -> bool:
        """Enqueue a request with the scheduler (stamped for queue-wait).

        Returns False — with a terminal StreamEvent queued for the next
        tick — when the request is turned away instead of enqueued:
        malformed (empty prompt -> ``finish_reason="error"``) or shed by
        backpressure (queue at ``max_queue`` under the ``reject`` policy,
        or under ``shed_lowest`` when the newcomer is itself the
        lowest-priority request waiting -> ``"rejected"``)."""
        if len(req.prompt) == 0 and req.rid not in self._swapped:
            # malformed: reject ALONE, loudly, before it can poison an
            # admission wave (an empty prompt would gather last_idx=-1)
            self.requests_invalid += 1
            self._terminal(req, FINISH_ERROR)
            return False
        if self.max_queue is not None and len(self.scheduler) >= self.max_queue:
            victim = None
            if self.shed_policy == "shed_lowest":
                shed = getattr(self.scheduler, "shed", None)
                if shed is not None:
                    victim = shed(below=int(getattr(req, "priority", 0)))
            if victim is None:
                # reject policy, no shed() hook, or the newcomer doesn't
                # outrank anyone waiting: the newcomer is turned away
                self.requests_rejected += 1
                self._terminal(req, FINISH_REJECTED)
                return False
            self._swapped.pop(victim.rid, None)
            self.requests_shed += 1
            self._terminal(victim, FINISH_REJECTED)
        if req.t_submit is None:
            req.t_submit = self._clock()
        self.scheduler.add(req)
        return True

    def cancel(self, rid: int) -> bool:
        """Evict a live slot or drop a queued request. The terminal
        ``cancelled`` StreamEvent is delivered on the next ``generate``
        tick. Returns False for unknown/finished rids."""
        req = self.scheduler.cancel(rid)
        if req is not None:
            self._swapped.pop(rid, None)  # preempted + requeued, now dead
            req.t_done = self._clock()
            self._pending_events.append(StreamEvent(
                rid, None, len(req.out), finished=True,
                finish_reason=FINISH_CANCELLED, stats=req.stats()))
            return True
        for s, r in enumerate(self.active):
            if r is not None and r.rid == rid:
                self._finish_slot(s, r, FINISH_CANCELLED, token=None)
                return True
        return False

    def preempt(self, rid: int) -> bool:
        """Swap a LIVE request out mid-flight: its slot's cache rows are
        copied to host (int8 codes / fp scales round-trip exactly) together
        with its stream state, the slot is freed, and the request goes back
        to the scheduler. On re-admission :meth:`_admit_group` scatters the
        rows back and decoding continues bit-identically — no re-prefill.
        Returns False for rids that aren't live."""
        for s, req in enumerate(self.active):
            if req is not None and req.rid == rid:
                break
        else:
            return False
        if self.paged:
            # gather the slot's BLOCKS (pool axis) to host, then release
            # them: the swap entry is self-contained, so the blocks can be
            # reused immediately — resume scatters into fresh blocks with
            # bit-identical contents
            blocks = list(self._slot_blocks[s])
            sub = jax.device_get(
                _take_slots(self.cache, jnp.asarray(blocks, jnp.int32)))
            self._swapped[rid] = {"cache": sub, "pos": int(self.pos[s]),
                                  "next_tok": int(self._next_tok[s]),
                                  "nblocks": len(blocks)}
            self.blocks_swapped += len(blocks)
            self._release_blocks(s, zero=False)
        else:
            sub = jax.device_get(
                _take_slots(self.cache, jnp.asarray([s], jnp.int32)))
            self._swapped[rid] = {"cache": sub, "pos": int(self.pos[s]),
                                  "next_tok": int(self._next_tok[s])}
        if self.spec:
            # the draft's slot rows ride the same swap entry, so resume
            # restores BOTH models' state with no draft re-prefill
            self._swapped[rid]["draft"] = jax.device_get(
                _take_slots(self.draft_cache, jnp.asarray([s], jnp.int32)))
        # free the slot WITHOUT finishing the request (no terminal event:
        # the stream simply pauses until resume)
        self.active[s] = None
        self._slot_stop[s] = frozenset()
        self._temp[s] = 0.0
        self._top_k[s] = 0
        self._top_p[s] = 1.0
        self._slot_draft_k[s] = 0
        req.preemptions += 1
        self.preemptions += 1
        self.scheduler.add(req)
        return True

    def _release_blocks(self, s: int, *, zero: bool) -> None:
        """Drop slot ``s``'s references into the block pool and clear its
        table row. ``zero=True`` (quarantine) first zeroes the blocks this
        slot holds EXCLUSIVELY — NaN is the one garbage the kv_len mask
        cannot neutralize (0 * NaN), so poisoned blocks must not reenter
        the free list dirty; shared blocks hold clean prompt codes some
        other holder is still reading."""
        from repro.serve import paged as paged_mod
        blocks = self._slot_blocks[s]
        if zero and blocks:
            exclusive = [b for b in blocks if self.pool.ref[b] == 1]
            if exclusive:
                self.cache = paged_mod.zero_blocks(self.cache, exclusive)
        for b in blocks:
            self.pool.decref(b)
        self._slot_blocks[s] = []
        self._table[s, :] = paged_mod.NULL_BLOCK

    def _resume_slot(self, req: Request, s: int) -> bool:
        """Scatter a swapped request's cache rows back into slot ``s`` and
        rebind its stream state. Lifecycle stamps are NOT reset — queue
        wait and TTFT stay measured from the original submission. Returns
        True when the slot was consumed; paged engines return False when
        the pool cannot supply the blocks right now (request requeued,
        swap entry kept) or the request can never fit (error-finished)."""
        sw = self._swapped[req.rid]
        if self.paged:
            from repro.serve.paged import PoolExhausted
            n = sw["nblocks"]
            if n > self.pool.capacity:
                # can NEVER fit: finish loudly instead of spinning forever
                self._swapped.pop(req.rid)
                self.pool_exhausted += 1
                self._terminal(req, FINISH_ERROR)
                return False  # slot stays free; terminal event queued
            blocks: list[int] = []
            try:
                for _ in range(n):
                    blocks.append(self.pool.alloc())
            except PoolExhausted:
                for b in blocks:
                    self.pool.decref(b)
                self.scheduler.add(req)  # retry when blocks free up
                return False
            self._swapped.pop(req.rid)
            self.cache = _put_slots(
                self.cache, jax.tree.map(jnp.asarray, sw["cache"]),
                jnp.asarray(blocks, jnp.int32))
            self._slot_blocks[s] = blocks
            self._table[s, :] = 0
            self._table[s, :len(blocks)] = blocks
        else:
            self._swapped.pop(req.rid)
            self.cache = _put_slots(
                self.cache, jax.tree.map(jnp.asarray, sw["cache"]),
                jnp.asarray([s], jnp.int32))
        if self.spec and "draft" in sw:
            self.draft_cache = _put_slots(
                self.draft_cache, jax.tree.map(jnp.asarray, sw["draft"]),
                jnp.asarray([s], jnp.int32))
        self._install_slot(s, req, self._resolve(req), pos=sw["pos"],
                           next_tok=sw["next_tok"])
        self.resumes += 1
        return True

    def generate(self, requests: Iterable[Request] = (),
                 ) -> Iterator[StreamEvent]:
        """Stream tokens for ``requests`` (plus anything already queued or
        live) until everything finishes. Yields one :class:`StreamEvent`
        per emitted token; terminal events carry finish reason + stats.
        Call :meth:`submit_request` (or pass more requests to a later
        ``generate``) to keep feeding the engine; call :meth:`cancel`
        between events to evict mid-stream."""
        for r in requests:
            self.submit_request(r)
        while (self._pending_events or len(self.scheduler)
               or any(r is not None for r in self.active)):
            yield from self._tick()

    def _tick(self) -> list[StreamEvent]:
        events = self._pending_events
        self._pending_events = []
        events += self._expire_live()
        self._maybe_preempt()
        events += self._pending_events  # preemption emits no events today,
        self._pending_events = []       # but a custom hook may cancel
        free = sum(r is None for r in self.active)
        if free and len(self.scheduler):
            wave = self._pop_wave(free, events)
            if wave:
                with TraceAnnotation(
                        "serve.admit", rids=[r.rid for r in wave],
                        bucket=self._bucket(max(len(r.prompt)
                                                for r in wave))):
                    events += self._admit_group(wave)
        if any(r is not None for r in self.active):
            events += self._step_events()
        return events

    def _expired(self, req: Request, now: float) -> bool:
        if (req.deadline_ms is not None and req.t_submit is not None
                and (now - req.t_submit) * 1e3 > req.deadline_ms):
            return True
        return (req.decode_timeout_ms is not None and req.t_first is not None
                and (now - req.t_first) * 1e3 > req.decode_timeout_ms)

    def _expire_live(self) -> list[StreamEvent]:
        """Finish live slots whose deadline/decode-timeout expired —
        BEFORE decoding another token on their behalf."""
        now = self._clock()
        events = []
        for s, req in enumerate(self.active):
            if req is not None and self._expired(req, now):
                self.deadline_expired += 1
                events.append(self._finish_slot(
                    s, req, FINISH_DEADLINE, token=None))
        return events

    def _pop_wave(self, free: int, events: list[StreamEvent]) -> list:
        """Pop the next admission wave, shedding queued requests whose
        deadline already expired (they would only waste a prefill)."""
        now = self._clock()
        wave: list = []
        while len(wave) < free and len(self.scheduler):
            for req in self.scheduler.pop(free - len(wave)):
                if self._expired(req, now):
                    self._swapped.pop(req.rid, None)
                    self.deadline_expired += 1
                    self._terminal(req, FINISH_DEADLINE)
                    events.append(self._pending_events.pop())  # deliver NOW
                else:
                    wave.append(req)
        return wave

    def _maybe_preempt(self) -> None:
        """Let the scheduler evict live work for higher-priority waiting
        work — only when the machine is actually full (free slots admit
        without anyone paying a swap)."""
        hook = getattr(self.scheduler, "should_preempt", None)
        if hook is None or not len(self.scheduler):
            return
        for _ in range(self.slots):
            if any(r is None for r in self.active):
                return
            live = [r for r in self.active if r is not None]
            rid = hook(live)
            if rid is None or not self.preempt(rid):
                return

    # --- admission --------------------------------------------------------
    def submit(self, req: Request) -> bool:
        return self.admit([req]) == 1

    def admit(self, reqs: list[Request]) -> int:
        """Admit as many of ``reqs`` (in order) as there are free slots,
        bypassing the scheduler (the closed-batch / legacy path).
        Returns the number actually admitted (malformed requests are
        rejected with a terminal ``error`` event, not counted)."""
        free = sum(r is None for r in self.active)
        group = reqs[:free]
        if not group:
            return 0
        inv0 = self.requests_invalid
        self._admit_group(group)
        return len(group) - (self.requests_invalid - inv0)

    def _admit_group(self, group: list[Request]) -> list[StreamEvent]:
        free = [s for s in range(self.slots) if self.active[s] is None]
        assert len(group) <= len(free), "scheduler over-popped"
        now = self._clock()
        events: list[StreamEvent] = []
        fresh: list[Request] = []
        for r in group:
            if r.rid in self._swapped:
                # preempted mid-flight: scatter its rows back, no prefill
                # (paged resume can fail allocation — slot stays free)
                if self._resume_slot(r, free[0]):
                    free.pop(0)
            elif len(r.prompt) == 0:
                # malformed: an empty prompt would gather last_idx=-1 (a
                # pad position) in the bucketed path. Reject it ALONE with
                # a terminal event — never abort a wave whose peers are
                # already stamped (this is the direct-admit() screen; the
                # queued path is screened at submit_request)
                self.requests_invalid += 1
                self._terminal(r, FINISH_ERROR)
                events.append(self._pending_events.pop())  # deliver NOW
            else:
                fresh.append(r)
        if self.paged and fresh:
            # allocate each prompt's block chain BEFORE the compiled wave;
            # requests the pool cannot hold right now go back to the
            # scheduler (decode progress frees blocks), and requests that
            # can NEVER fit are error-finished instead of spinning
            admitted: list[Request] = []
            from repro.serve.paged import PoolExhausted
            for r in fresh:
                s = free[len(admitted)]  # the slot zip() will pair r with
                try:
                    blocks = self.pool.alloc_prompt(r.prompt)
                except PoolExhausted:
                    if -(-len(r.prompt) // self.block_size) > \
                            self.pool.capacity:
                        self.pool_exhausted += 1
                        self._terminal(r, FINISH_ERROR)
                        events.append(self._pending_events.pop())
                    else:
                        self.scheduler.add(r)  # retry when blocks free
                    continue
                self._slot_blocks[s] = blocks
                self._table[s, :] = 0
                self._table[s, :len(blocks)] = blocks
                admitted.append(r)
            fresh = admitted
        if not fresh:
            return events
        for r in fresh:
            if r.t_submit is None:
                r.t_submit = now  # direct admit(): no queue wait
            r.t_admit = now
        free = free[: len(fresh)]
        if self.cfg.family in ("ssm", "hybrid"):
            # recurrent state integrates every fed token: no pad buckets;
            # chunk ladder instead (bounded compiled shapes)
            for req, s in zip(fresh, free):
                events += self._admit_chunked(req, s)
            return events
        return events + self._admit_bucketed(fresh, free)

    def _group_sampling(self, group: list[Request]):
        """Per-request device vectors for one admission wave. Returns
        (resolved params, keys (G,2)|None, temp, top_k, top_p) — keys is
        None when the whole wave is greedy (PRNG-free prefill trace), and
        the filter vectors are None when unused (no top_mask in the
        trace)."""
        sps = [self._resolve(r) for r in group]
        if all(sp.greedy for sp in sps):
            return sps, None, None, None, None
        keys = np.stack([sp.key_data(engine_seed=self.seed, rid=r.rid)
                         for sp, r in zip(sps, group)])
        temp = jnp.asarray([sp.temperature for sp in sps], jnp.float32)
        top_k, top_p = self._filter_vectors(
            (sp.top_k for sp in sps), (sp.top_p for sp in sps))
        return sps, jnp.asarray(keys), temp, top_k, top_p

    @staticmethod
    def _filter_vectors(ks, ps):
        """Per-row top-k/top-p device vectors — or None for a filter no
        row is using, keeping it (and its full-vocab sort) out of the
        jitted step. Freed slots are reset to the inert 0 / 1.0, so
        passing every slot's value is safe on the decode path."""
        ks, ps = list(ks), list(ps)
        top_k = jnp.asarray(ks, jnp.int32) if any(k > 0 for k in ks) else None
        top_p = jnp.asarray(ps, jnp.float32) if any(p < 1.0 for p in ps) \
            else None
        return top_k, top_p

    def _bucket(self, max_plen: int) -> int:
        pad = (-max_plen) % self.prompt_pad
        # cap padding so the padded prompt always fits the cache
        return max_plen + min(pad, max(0, self.max_len - 1 - max_plen))

    def _admit_bucketed(self, group: list[Request],
                        free: list[int]) -> list[StreamEvent]:
        """Attention-family admission: every free slot in ONE padded-bucket
        compiled call (zero + prefill + first-token sample fused)."""
        plens = [int(len(r.prompt)) for r in group]
        bucket = self._bucket(max(plens))
        toks = np.stack([np.pad(np.asarray(r.prompt, np.int32),
                                (0, bucket - p))
                         for r, p in zip(group, plens)])
        sps, keys, temp, top_k, top_p = self._group_sampling(group)
        table = jnp.asarray(self._table[free]) if self.paged else None
        self.cache, tok, last = self._jit_prefill(
            self.params, self.cache, jnp.asarray(toks),
            jnp.asarray(free, jnp.int32),
            jnp.asarray([p - 1 for p in plens], jnp.int32),
            jnp.zeros(len(group), jnp.int32),
            keys, temp, top_k, top_p, table, plen=bucket, fresh=True)
        if self.spec:
            # the draft consumes the SAME padded bucket (one compiled
            # shape family per bucket for both models); its pad writes sit
            # behind the kv_len mask like the target's
            self.draft_cache = self._jit_draft_prefill(
                self.draft_params, self.draft_cache, jnp.asarray(toks),
                jnp.asarray(free, jnp.int32),
                jnp.zeros(len(group), jnp.int32))
        return self._finish_admission(group, free, plens, sps, tok, last)

    def _admit_chunked(self, req: Request, s: int) -> list[StreamEvent]:
        """SSM/hybrid admission: exact-length feeding via a power-of-two
        chunk ladder with state threaded between compiled calls."""
        prompt = np.asarray(req.prompt, np.int32)
        plen = int(len(prompt))
        sizes, rem = [], plen
        while rem:
            c = self.prompt_chunk
            while c > rem:
                c //= 2
            sizes.append(c)
            rem -= c
        off, fresh = 0, True
        slot = jnp.asarray([s], jnp.int32)
        sps, keys, temp, top_k, top_p = self._group_sampling([req])
        for c in sizes:
            self.cache, tok, last = self._jit_prefill(
                self.params, self.cache, jnp.asarray(prompt[None, off:off + c]),
                slot, jnp.asarray([c - 1], jnp.int32),
                jnp.asarray([off], jnp.int32),
                keys, temp, top_k, top_p, plen=c, fresh=fresh)
            fresh = False
            off += c
        return self._finish_admission([req], [s], [plen], sps, tok, last)

    def _finish_admission(self, group, free, plens, sps, tok,
                          last) -> list[StreamEvent]:
        if self.sample_on_host:
            firsts = [int(jnp.argmax(last[g])) for g in range(len(group))]
            self.host_syncs += len(group)
        else:
            with TraceAnnotation("serve.prefill_sync"):
                firsts = np.asarray(tok)
            self.host_syncs += 1
        now = self._clock()
        events = []
        for g, (req, s) in enumerate(zip(group, free)):
            first = int(firsts[g])
            self._install_slot(s, req, sps[g], pos=plens[g], next_tok=first)
            req.out.append(first)
            req.t_first = now
            events.append(self._emit(s, req, first))
        return events

    def _install_slot(self, s: int, req: Request, sp: SamplingParams, *,
                      pos: int, next_tok: int) -> None:
        """Bind a request to a slot: position counter + per-slot sampling
        state (shared by fresh admission and preemption resume)."""
        self.pos[s] = pos
        self.active[s] = req
        self._slot_stop[s] = sp.stop_set(self.eos_id)
        self._slot_max_new[s] = int(sp.max_new)
        self._temp[s] = sp.temperature
        self._top_k[s] = sp.top_k
        self._top_p[s] = sp.top_p
        self._keys[s] = sp.key_data(engine_seed=self.seed, rid=req.rid)
        self._slot_draft_k[s] = self._spec_k_for(req)
        self._next_tok[s] = next_tok

    # --- decode -----------------------------------------------------------
    def _step_events(self) -> list[StreamEvent]:
        """One decode step for every active slot -> one StreamEvent per
        emitted token (terminal events carry finish reason + stats).
        Speculative engines run a propose/verify/commit WINDOW instead of
        a single token; both paths share :meth:`_commit_slot`."""
        if self.spec:
            return self._spec_step_events()
        if self.faults is not None:
            self.faults.before_decode(self)
        with TraceAnnotation("serve.decode_prep"):
            events0: list[StreamEvent] = []
            if self.paged:
                # grow block chains for slots whose next write crosses a
                # block boundary (preempting victims on a dry pool);
                # exhaustion can finish slots, so re-check liveness before
                # decoding
                events0 = self._ensure_decode_blocks()
                if not any(r is not None for r in self.active):
                    return events0
            live = [s for s, r in enumerate(self.active) if r is not None]
            self.max_concurrent = max(self.max_concurrent, len(live))
            toks = jnp.asarray(self._next_tok[:, None])
            positions = jnp.asarray(self.pos)
            table = jnp.asarray(self._table) if self.paged else None
            if self.sample_on_host or all(self._temp[s] <= 0 for s in live):
                keys = gen = temp = top_k = top_p = None  # argmax-only trace
            else:
                gen = jnp.asarray([len(r.out) if r is not None else 0
                                   for r in self.active], jnp.int32)
                keys = jnp.asarray(self._keys)
                temp = jnp.asarray(self._temp)
                # filters stay OUT of the trace when no live slot uses
                # them: a temperature-only batch shouldn't pay top_mask's
                # full-vocab sort+cumsum every step
                top_k, top_p = self._filter_vectors(self._top_k, self._top_p)
            probe = jax.tree.leaves(self.cache)
        with TraceAnnotation("serve.decode_dispatch"):
            if self.sample_on_host:
                logits, self.cache = self._jit_decode_logits(
                    self.params, self.cache, toks, positions, table)
            else:
                tok_dev, self.cache = self._jit_decode(
                    self.params, self.cache, toks, positions,
                    keys, gen, temp, top_k, top_p, table)
        tok_np = None
        if not self.sample_on_host:
            with TraceAnnotation("serve.decode_sync",
                                 step=self.decode_steps + 1,
                                 live=len(live)):
                tok_np = np.asarray(tok_dev)  # THE step's one transfer
            self.host_syncs += 1
        self.decode_steps += 1
        with TraceAnnotation("serve.commit"):
            # EVERY leaf must donate — a partially-donated cache (some
            # planes copied, e.g. mixed int8/fp16/fp32 leaves under
            # kv_quant) still burns bandwidth and must show up in the counter
            self.cache_donated = all(a.is_deleted() for a in probe)
            if not self.cache_donated:  # functional copy happened: count it
                self.cache_bytes_moved += self._cache_nbytes
            if self.watchdog is not None:
                now = self._clock()
                self.stalled_steps += len(self.watchdog.failed(now))
                self.watchdog.beat(0, self.decode_steps, now=now)
            events = events0
            for s, req in enumerate(self.active):
                if req is None:
                    continue
                if tok_np is None:
                    row = np.asarray(logits[s])  # one transfer per slot
                    self.host_syncs += 1
                    tok = _POISONED if not np.isfinite(row).all() \
                        else int(np.argmax(row))
                else:
                    tok = int(tok_np[s])
                events += self._commit_slot(s, req, [tok])
        return events

    def _spec_step_events(self) -> list[StreamEvent]:
        """One speculative window for every active slot: the draft
        proposes K candidates from its own cache, ONE batched target pass
        verifies all K+1 window positions, and each slot commits its
        accepted prefix plus one window-end token. Every single-token
        invariant generalizes per-slot-variable-count: one device->host
        transfer moves the whole (S, K+1) window + commit counts, both
        caches donate in place, quarantine rides the same _POISONED
        sentinel, and kvec=0 slots (draft opt-out) commit exactly one
        token through the identical machinery."""
        if self.faults is not None:
            self.faults.before_decode(self)
        events0: list[StreamEvent] = []
        if self.paged:
            events0 = self._ensure_decode_blocks()
            if not any(r is not None for r in self.active):
                return events0
        n_live = sum(r is not None for r in self.active)
        self.max_concurrent = max(self.max_concurrent, n_live)
        kvec_np = self._slot_draft_k.copy()
        toks = jnp.asarray(self._next_tok[:, None])
        positions = jnp.asarray(self.pos)
        table = jnp.asarray(self._table) if self.paged else None
        live = [s for s, r in enumerate(self.active) if r is not None]
        if all(self._temp[s] <= 0 for s in live):
            keys = gen = temp = top_k = top_p = None  # argmax-only traces
        else:
            gen = jnp.asarray([len(r.out) if r is not None else 0
                               for r in self.active], jnp.int32)
            keys = jnp.asarray(self._keys)
            temp = jnp.asarray(self._temp)
            top_k, top_p = self._filter_vectors(self._top_k, self._top_p)
        probe = jax.tree.leaves(self.cache)
        dprobe = jax.tree.leaves(self.draft_cache)
        cand, qlog, self.draft_cache = self._jit_propose(
            self.draft_params, self.draft_cache, toks, positions,
            keys, gen, temp, top_k, top_p)
        out_dev, n_dev, self.cache = self._jit_verify(
            self.params, self.cache, cand, positions,
            jnp.asarray(kvec_np), keys, gen, temp, top_k, top_p, qlog,
            table)
        out_np, n_np = jax.device_get((out_dev, n_dev))  # THE one transfer
        self.host_syncs += 1
        self.decode_steps += 1
        self.spec_steps += 1
        # both models' caches must donate for the window to be copy-free
        self.cache_donated = (all(a.is_deleted() for a in probe)
                              and all(a.is_deleted() for a in dprobe))
        if not self.cache_donated:
            self.cache_bytes_moved += self._cache_nbytes
        if self.watchdog is not None:
            now = self._clock()
            self.stalled_steps += len(self.watchdog.failed(now))
            self.watchdog.beat(0, self.decode_steps, now=now)
        events = events0
        for s, req in enumerate(self.active):
            if req is None:
                continue
            n = int(n_np[s])
            window = [int(t) for t in out_np[s, :n]]
            if window[0] != _POISONED:
                # acceptance accounting: n - 1 of the kvec proposals were
                # committed (the window-end token is the engine's, not the
                # draft's), counted even when a stop/length finish inside
                # the window drops the tail of the stream
                kv = int(kvec_np[s])
                self.draft_proposed += kv
                self.draft_accepted += n - 1
                req.drafted += kv
                req.accepted += n - 1
                req.spec_windows += 1
            events += self._commit_slot(s, req, window)
        return events

    def _commit_slot(self, s: int, req: Request,
                     toks: list) -> list[StreamEvent]:
        """Fold committed tokens into one slot's stream state — shared by
        the one-token step (a 1-element window) and the speculative
        window. Stops at the first terminal condition: a _POISONED
        sentinel quarantines the slot (finish_reason="error", cache rows
        re-zeroed), a stop/length finish drops the rest of the window (the
        cache holds a few positions past the stream's end; they are never
        read — kv_len follows ``pos``, which stops advancing)."""
        events: list[StreamEvent] = []
        for tok in toks:
            if tok == _POISONED:
                # numeric quarantine: the slot's logits went non-finite.
                # Finish the stream loudly and re-zero the slot's cache
                # rows so the poison can't leak into a later tenant.
                self.quarantined += 1
                events.append(self._finish_slot(
                    s, req, FINISH_ERROR, token=None))
                self._zero_slot(s)
                break
            req.out.append(tok)
            self._next_tok[s] = tok
            self.pos[s] += 1
            self.tokens_decoded += 1
            ev = self._emit(s, req, tok)
            events.append(ev)
            if ev.finished:
                break
        return events

    def _ensure_decode_blocks(self) -> list[StreamEvent]:
        """Paged decode admission control: before each step, every live
        slot must own the block its next write lands in. On a dry pool,
        preempt a victim (lowest priority, newest admission) to free its
        blocks; when no victim exists the slot itself error-finishes — the
        pool physically cannot hold it."""
        from repro.serve.paged import PoolExhausted, blocks_needed
        events: list[StreamEvent] = []
        for s, req in enumerate(self.active):
            if req is None:
                continue
            # speculative slots pre-extend by their window lookahead: the
            # window can commit (and later read) positions up to
            # pos + kvec. Verify writes BEYOND pos + kvec (up to the
            # engine-wide K) land in the null block — never committed,
            # never read, finite garbage by the paged invariant.
            need = blocks_needed(self.pos[s], self.block_size,
                                 lookahead=int(self._slot_draft_k[s]))
            while len(self._slot_blocks[s]) < need:
                try:
                    blk = self.pool.alloc()
                except PoolExhausted:
                    victim = self._pick_victim(exclude=s)
                    if victim is not None and self.preempt(victim):
                        continue  # victim's blocks are free now: retry
                    self.pool_exhausted += 1
                    events.append(self._finish_slot(
                        s, req, FINISH_ERROR, token=None))
                    break  # _finish_slot released this slot's blocks
                self._slot_blocks[s].append(blk)
                self._table[s, len(self._slot_blocks[s]) - 1] = blk
        return events

    def _pick_victim(self, *, exclude: int) -> Optional[int]:
        """rid of the live request to preempt when the pool runs dry:
        lowest priority first, newest admission breaks ties (it has the
        least sunk prefill work)."""
        best = None
        for s, r in enumerate(self.active):
            if r is None or s == exclude:
                continue
            key = (int(getattr(r, "priority", 0)), -(r.t_admit or 0.0))
            if best is None or key < best[0]:
                best = (key, r.rid)
        return best[1] if best else None

    def _zero_slot(self, s: int) -> None:
        """Eagerly re-zero one slot's cache rows (quarantine cleanup).
        Paged engines already zeroed + freed the poisoned blocks in
        ``_release_blocks`` (via ``_finish_slot``); only the host-side
        counters remain."""
        if not self.paged:
            self.cache = _put_slots(self.cache,
                                    _zero_slots_like(self.cache, 1),
                                    jnp.asarray([s], jnp.int32))
        if self.spec:
            # the draft cache is dense even on paged engines; a poisoned
            # slot's draft rows are re-zeroed for the same reason its
            # target rows are (NaN is the garbage no mask neutralizes)
            self.draft_cache = _put_slots(self.draft_cache,
                                          _zero_slots_like(self.draft_cache,
                                                           1),
                                          jnp.asarray([s], jnp.int32))
        self.pos[s] = 0
        self._next_tok[s] = 0

    def _emit(self, s: int, req: Request, tok: int) -> StreamEvent:
        """Record one emitted token; finishes the slot on stop/length."""
        idx = len(req.out) - 1
        if tok in self._slot_stop[s]:
            return self._finish_slot(s, req, FINISH_STOP, token=tok)
        if (len(req.out) >= self._slot_max_new[s]
                or self.pos[s] >= self.max_len - 1):
            return self._finish_slot(s, req, FINISH_LENGTH, token=tok)
        return StreamEvent(req.rid, tok, idx)

    def _finish_slot(self, s: int, req: Request, reason: str,
                     token: Optional[int]) -> StreamEvent:
        req.done = True
        req.finish_reason = reason
        req.t_done = self._clock()
        if self.paged:
            # blocks return to the pool the moment the stream ends;
            # quarantine (reason="error") zeroes exclusively-held blocks
            # first so NaN never reenters circulation
            self._release_blocks(s, zero=(reason == FINISH_ERROR))
        self.active[s] = None
        self._slot_stop[s] = frozenset()
        self._temp[s] = 0.0
        self._top_k[s] = 0
        self._top_p[s] = 1.0
        self._slot_draft_k[s] = 0
        # tokenless terminal events (cancellation) index PAST the stream:
        # len(out), the position no token will ever fill — so (rid, index)
        # never collides with a real token's event
        idx = len(req.out) - 1 if token is not None else len(req.out)
        ev = StreamEvent(req.rid, token, idx, finished=True,
                         finish_reason=reason, stats=req.stats())
        if reason == FINISH_CANCELLED:
            self._pending_events.append(ev)
        return ev

    def step(self) -> list[tuple[int, int]]:
        """One decode step for every active slot; returns [(rid, token)]
        (legacy view of :meth:`_step_events`)."""
        if not any(r is not None for r in self.active):
            return []
        return [(e.rid, e.token) for e in self._step_events()
                if e.token is not None]

    def run(self, requests: list[Request]) -> list[Request]:
        """Drive all requests to completion with continuous admission —
        the closed-batch shim over :meth:`generate` (FIFO ordering via the
        engine's scheduler; benchmarks use it for token-parity baselines)."""
        for _ in self.generate(requests):
            pass
        return requests

    @property
    def cache_bytes(self) -> int:
        """Total bytes held by the slot cache (KV planes + scale planes +
        recurrent state). Benchmarks and tests assert the rotated-int8
        shrink against this instead of poking cache internals."""
        return int(sum(a.nbytes for a in jax.tree.leaves(self.cache)))

    def stats(self) -> dict:
        """Perf counters for the bench harness. ``cache_bytes_per_token``
        counts only the per-token self-attention KV planes — SSM/hybrid
        recurrent state and the audio cross-attention memory are O(1) in
        decoded tokens, so folding them in would misprice long contexts
        (an attention-free arch reports 0)."""
        attn = self.cache.get("attn", {})
        attn_bytes = sum(a.nbytes for a in jax.tree.leaves(attn))
        if self.paged:
            # pool planes are (L, NB, KV, BS, *): NB * BS addressable
            # positions, shared by every slot
            n_tokens_cap = self.num_blocks * self.block_size
        else:
            # divide by the buffer's REAL position count (frontend archs
            # allocate max_len + frontend_len slots), not max_len, so the
            # vision prefix isn't misbilled as per-decoded-token cost
            n_pos = attn["k"].shape[3] if attn else 1
            n_tokens_cap = self.slots * n_pos
        bytes_per_token = attn_bytes / max(n_tokens_cap, 1)
        # reserved: bytes requests currently CLAIM (a dense engine claims
        # its full B x max_len allocation for the engine's life; a paged
        # engine claims only allocated blocks). live: pos-weighted bytes of
        # tokens actually written — the gap between the two is the
        # reservation waste the paged pool exists to reclaim.
        live_tokens = int(sum(int(self.pos[s])
                              for s, r in enumerate(self.active)
                              if r is not None))
        if self.paged:
            reserved = bytes_per_token * self.pool.used() * self.block_size
        else:
            reserved = attn_bytes
        out = {
            "host_syncs": self.host_syncs,
            "tokens_decoded": self.tokens_decoded,
            "syncs_per_token": (self.host_syncs / self.tokens_decoded
                                if self.tokens_decoded else float("nan")),
            "cache_bytes": self.cache_bytes,
            "cache_bytes_reserved": int(reserved),
            "cache_bytes_live": int(bytes_per_token * live_tokens),
            "cache_bytes_per_token": bytes_per_token,
            "decode_steps": self.decode_steps,
            "cache_donated": self.cache_donated,
            "cache_bytes_moved": self.cache_bytes_moved,
            "scheduler": getattr(self.scheduler, "name",
                                 type(self.scheduler).__name__),
            "waiting": len(self.scheduler),
            # --- resilience counters ---
            "requests_rejected": self.requests_rejected,
            "requests_shed": self.requests_shed,
            "requests_invalid": self.requests_invalid,
            "deadline_expired": self.deadline_expired,
            "quarantined": self.quarantined,
            "preemptions": self.preemptions,
            "resumes": self.resumes,
            "stalled_steps": self.stalled_steps,
            "swapped": len(self._swapped),
            "max_queue": self.max_queue,
            "shed_policy": self.shed_policy,
            # --- compute-path knobs (which numeric paths served this run) ---
            "backend": self.rt.backend,
            **self._paths,
            "kv_quant": self.rt.kv_quant,
            "act_quant": self.rt.act_quant,
            "max_concurrent": self.max_concurrent,
        }
        if self.spec:
            out.update(
                speculative=True,
                num_draft_tokens=self._spec_k,
                spec_steps=self.spec_steps,
                draft_proposed=self.draft_proposed,
                draft_accepted=self.draft_accepted,
                acceptance_rate=(self.draft_accepted / self.draft_proposed
                                 if self.draft_proposed else float("nan")),
                tokens_per_step=(self.tokens_decoded / self.decode_steps
                                 if self.decode_steps else float("nan")),
                draft_cache_bytes=int(sum(
                    a.nbytes for a in jax.tree.leaves(self.draft_cache))),
            )
        if self.paged:
            out.update(
                paged=True,
                block_size=self.block_size,
                pool_blocks=self.pool.capacity,
                pool_blocks_used=self.pool.used(),
                pool_utilization=round(self.pool.utilization(), 4),
                blocks_swapped=self.blocks_swapped,
                pool_exhausted=self.pool_exhausted,
                prefix_hits=self.pool.prefix_hits,
            )
        if self.mesh is not None:
            from repro.serve import tp as tp_mod
            out["devices"] = self.mesh.devices.size
            out["cache_bytes_per_device"] = tp_mod.cache_bytes_per_device(
                self.cache)
            out["tp_shard_map"] = self.rt.tp_shard_map
        return out


@jax.named_scope("sample")
def _sample_slots(last, keys, gen, temp, top_k, top_p):
    """Per-slot sampling inside the jitted step. ``keys`` (G, 2) are the
    requests' BASE keys; each row folds in its own request-local token
    index ``gen`` so the draw depends only on (request seed, token index) —
    never on the slot, the step, or the batchmates (the bit-parity
    contract). ``keys=None`` is the all-greedy fast path: bare argmax, no
    PRNG in the trace."""
    if keys is None:
        return lm.sample_tokens(last)
    step_keys = jax.vmap(jax.random.fold_in)(keys, gen)
    return lm.sample_tokens(last, step_keys, temp, top_k=top_k, top_p=top_p)


# --- slot gather/scatter over heterogeneous cache pytrees -------------------

def _batch_axis(a) -> int:
    """Cache leaves are either (L, B, ...) stacked per layer or (B, ...)."""
    return 1 if a.ndim >= 3 else 0


def _take_slots(cache, slots):
    """Gather the (G,)-slot sub-cache along each leaf's batch axis."""
    return jax.tree.map(
        lambda a: jnp.take(a, slots, axis=_batch_axis(a)), cache)


def _zero_slots_like(cache, g: int):
    """A fresh zero state for G slots (shape of a gathered sub-cache)."""
    def zero(a):
        ax = _batch_axis(a)
        shape = a.shape[:ax] + (g,) + a.shape[ax + 1:]
        return jnp.zeros(shape, a.dtype)
    return jax.tree.map(zero, cache)


def _put_slots(cache, part, slots):
    """Scatter a (G,)-slot sub-cache back into the full cache."""
    def put(full, p):
        ax = _batch_axis(full)
        fm = jnp.moveaxis(full, ax, 0)
        pm = jnp.moveaxis(p.astype(full.dtype), ax, 0)
        return jnp.moveaxis(fm.at[slots].set(pm), 0, ax)
    return jax.tree.map(put, cache, part)
