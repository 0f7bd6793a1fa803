"""A whole run on the CPU with the look for a chip skipped: the served
path, the metrics and the comparison with the reference; then the same
run with the served path broken, which the comparison has to catch."""
import json

import jax
import pytest

import run as bench_run
from harness.spec import Registry


def _cpu_as_chip(monkeypatch):
    devs = jax.devices()
    monkeypatch.setattr(bench_run, "device_info", lambda reg, chips: (
        {"platform": "tpu", "kind": "TPU v5 lite", "count": chips}, devs))
    monkeypatch.setattr(bench_run, "check_paths", lambda stats: None)


def _result(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["tiny.chat", "tiny.long"])
def test_run_is_correct(cell, tiny_root, monkeypatch, capsys):
    _cpu_as_chip(monkeypatch)
    rc = bench_run.main(["--workload", cell, "--seed", str(2**33 + 5),
                         "--seconds", "2", "--trace", "0"],
                        reg=Registry(tiny_root))
    out = _result(capsys)
    assert rc == 0
    assert out["correct"] is True, out
    assert list(out)[-1] == "checks"
    assert out["checks"]["max_logit_gap"]["value"] <= 1e-3
    assert out["checks"]["mean_logit_gap"]["value"] <= 1e-5
    names = {m["name"] for m in Registry(tiny_root).metrics_for(
        cell, "end_to_end")}
    assert set(out["metrics"]) == names
    assert out["metrics"]["output_tok_s"]["value"] > 0
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["memory_peak_bytes"] >= 0


def test_traced_run(tiny_root, monkeypatch, capsys):
    """``--trace 1`` drives the profiler and reports the per-layer metrics
    it can read (a CPU trace holds no TPU operations)."""
    _cpu_as_chip(monkeypatch)
    rc = bench_run.main(["--workload", "tiny.chat", "--seed", "4",
                         "--seconds", "2", "--trace", "1"],
                        reg=Registry(tiny_root))
    out = _result(capsys)
    assert rc == 0 and out["correct"] is True
    assert out["metrics"]["compiles_in_window"]["value"] == 0
    assert "queue_wait_p95_ms" in out["metrics"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["device"]["window_s"] > 0


def test_no_chip_no_result(tiny_root, capsys):
    rc = bench_run.main(["--workload", "tiny.chat", "--seed", "1",
                         "--seconds", "1", "--trace", "0"],
                        reg=Registry(tiny_root))
    assert rc == 2
    assert capsys.readouterr().out == ""


def _alter_token(eng_cls, monkeypatch):
    """Every decoded token of slot 0 comes out one id higher."""
    orig = eng_cls._decode_impl

    def altered(self, *a, **kw):
        tok, cache = orig(self, *a, **kw)
        return tok.at[0].set((tok[0] + 1) % self.cfg.vocab_size), cache
    monkeypatch.setattr(eng_cls, "_decode_impl", altered)


def _stale_cache(eng_cls, monkeypatch):
    """The decode step hands back its cache unchanged: no token is
    written to the cache."""
    orig = eng_cls._decode_impl

    def stale(self, params, cache, *a, **kw):
        tok, _ = orig(self, params, cache, *a, **kw)
        return tok, cache
    monkeypatch.setattr(eng_cls, "_decode_impl", stale)


@pytest.mark.parametrize("fault", [_alter_token, _stale_cache])
def test_broken_path_is_not_correct(fault, tiny_root, monkeypatch, capsys):
    from repro.serve.engine import ServeEngine
    _cpu_as_chip(monkeypatch)
    fault(ServeEngine, monkeypatch)
    rc = bench_run.main(["--workload", "tiny.chat", "--seed", "11",
                         "--seconds", "2", "--trace", "0"],
                        reg=Registry(tiny_root))
    out = _result(capsys)
    assert rc == 0
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("control", ["fp8", "bf16", "w3a8"])
def test_control_is_not_correct(control, tiny_root, monkeypatch, capsys):
    """Each control put in the program's place, through the run's own
    comparison, comes out not correct; the program on the same seed is
    correct."""
    _cpu_as_chip(monkeypatch)
    argv = ["--workload", "tiny.chat", "--seed", "21", "--seconds", "2",
            "--trace", "0"]
    assert bench_run.main(argv, reg=Registry(tiny_root)) == 0
    assert _result(capsys)["correct"] is True
    rc = bench_run.main(argv + ["--control", control],
                        reg=Registry(tiny_root))
    out = _result(capsys)
    assert rc == 0
    assert out["correct"] is False, out["checks"]
