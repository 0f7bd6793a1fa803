"""Capture pre-speculative-decoding golden engine streams.

Run ONCE against the engine at the commit BEFORE the propose/verify/commit
refactor landed. tests/test_spec_decode.py replays the same request set
through the refactored engine with speculative decoding OFF and asserts the
streams are byte-identical to these goldens (the refactor must be a no-op
when no draft model is configured), and with GREEDY speculative decoding ON
asserts the committed token sequences are identical (lossless
verification).

Covers the three cache layouts the engine serves: dense fp32, dense
rotated-int8 (kv_quant), and the paged block pool — each with greedy and
sampled requests mixed in one burst.

    PYTHONPATH=src python tests/goldens/capture_spec_goldens.py
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
    """Heterogeneous burst: varied prompt/output lengths, greedy and
    sampled (temperature / top-k / top-p) requests, plus a stop-token
    request so stop handling is pinned too."""
    rng = np.random.default_rng(11)
    plens = [4, 9, 17, 6, 12, 21, 3]
    maxn = [7, 10, 5, 9, 6, 12, 8]
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, vocab, size=p).astype(np.int32),
                    max_new=m)
            for i, (p, m) in enumerate(zip(plens, maxn))]
    reqs.append(Request(
        rid=200, prompt=rng.integers(0, vocab, size=8).astype(np.int32),
        sampling=SamplingParams(temperature=0.8, seed=77, max_new=9)))
    reqs.append(Request(
        rid=201, prompt=rng.integers(0, vocab, size=5).astype(np.int32),
        sampling=SamplingParams(temperature=1.1, top_k=20, seed=13,
                                max_new=8)))
    reqs.append(Request(
        rid=202, prompt=rng.integers(0, vocab, size=7).astype(np.int32),
        sampling=SamplingParams(temperature=0.9, top_p=0.85, seed=5,
                                max_new=8)))
    reqs.append(Request(
        rid=203, prompt=rng.integers(0, vocab, size=6).astype(np.int32),
        sampling=SamplingParams(max_new=10, stop=(7, 42))))
    return reqs


def capture(params, cfg, **engine_kw):
    eng = ServeEngine(params, cfg, slots=4, max_len=64, prompt_pad=16,
                      **engine_kw)
    done = eng.run(golden_requests(cfg.vocab_size))
    return {str(r.rid): [int(t) for t in r.out] for r in done}


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
    doc = {
        "dense_fp": capture(params, cfg,
                            rt=Runtime(compute_dtype=jnp.float32)),
        "dense_q8": capture(params, cfg,
                            rt=Runtime(compute_dtype=jnp.float32,
                                       kv_quant=True)),
        "paged_q8": capture(params, cfg,
                            rt=Runtime(compute_dtype=jnp.float32,
                                       kv_quant=True),
                            paged=True),
    }
    path = os.path.join(os.path.dirname(__file__), "spec_decode_streams.json")
    _store(path, doc)
    n = sum(len(v) for layout in doc.values() for v in layout.values())
    print(f"wrote {path}: {n} tokens over "
          f"{sum(len(v) for v in doc.values())} streams x {len(doc)} layouts")


if __name__ == "__main__":
    main()
