"""Capture dense-engine golden token streams for the paged-KV parity test.

Run ONCE against the dense (pre-paging) engine; tests/test_paged.py replays
the same request set through ServeEngine(paged=True) and asserts the token
streams are bit-identical to these committed goldens.

    PYTHONPATH=src python tests/goldens/capture_paged_goldens.py
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, reduced
from repro.models import lm
from repro.models.layers import Runtime
from repro.serve.engine import Request, ServeEngine
from repro.serve.sampling import SamplingParams


def golden_requests(vocab):
    """Heterogeneous-length burst incl. a shared 16-token prefix pair
    (prefix-sharing coverage) and one sampled request (PRNG parity)."""
    rng = np.random.default_rng(7)
    plens = [3, 9, 17, 5, 12, 24, 7, 2]
    maxn = [6, 10, 4, 8, 5, 12, 9, 7]
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, vocab, size=p).astype(np.int32),
                    max_new=m)
            for i, (p, m) in enumerate(zip(plens, maxn))]
    shared = rng.integers(0, vocab, size=16).astype(np.int32)
    reqs.append(Request(rid=100, prompt=shared.copy(), max_new=6))
    reqs.append(Request(rid=101, prompt=np.concatenate(
        [shared, rng.integers(0, vocab, size=3).astype(np.int32)]),
        max_new=6))
    reqs.append(Request(
        rid=102, prompt=rng.integers(0, vocab, size=6).astype(np.int32),
        sampling=SamplingParams(temperature=0.8, seed=123, max_new=8)))
    return reqs


def _store(path, streams):
    """Write ``streams`` under this process's threefry bit layout (the
    weights, hence the streams, differ per layout; tests/_prng.py), keeping
    the other layout's entry."""
    layout = ("partitionable" if jax.config.jax_threefry_partitionable
              else "legacy")
    doc = {}
    if os.path.exists(path):
        with open(path) as f:
            doc = json.load(f)
    doc[layout] = streams
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)


def main():
    cfg = reduced(get_config("smollm-135m"))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rt = Runtime(compute_dtype=jnp.float32, kv_quant=True)
    eng = ServeEngine(params, cfg, slots=4, max_len=64, prompt_pad=16, rt=rt)
    done = eng.run(golden_requests(cfg.vocab_size))
    streams = {str(r.rid): [int(t) for t in r.out] for r in done}
    path = os.path.join(os.path.dirname(__file__), "paged_dense_streams.json")
    _store(path, streams)
    print(f"wrote {path}: "
          f"{sum(len(v) for v in streams.values())} tokens over "
          f"{len(streams)} streams")


if __name__ == "__main__":
    main()
