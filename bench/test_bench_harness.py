"""CPU rehearsal of the benchmark at smoke size: each traffic driver runs a
whole cell (set-up, window, reference comparison) with the look for a chip
skipped; a broken timed path and the lower-precision control both make
``correct`` false; and a new cell, configuration, mix and metric are found
by name from new files alone."""
import json
import shutil

import jax
import pytest

from bench import run, smoke
from bench.reference import ssm_lm

PERSONALIZE = "mamba2-130m.personalize"
DECODE = "mamba2-130m.decode"
SEED = 2 ** 31 + 77          # seeds run past 32 signed bits


def _run(workload, hook=None, trace=False, spec=None):
    spec = spec or smoke.smoke_spec(workload)
    return run.run_cell(spec, SEED, 0.5, trace, require_chip=False,
                        driver_hook=hook)


@pytest.mark.parametrize("workload", [PERSONALIZE, DECODE])
def test_cell_runs_correct_on_cpu(workload):
    res = _run(workload)
    spec = run.cell_spec(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    json.dumps(res)


def test_traced_run_reads_no_device_metric_on_cpu():
    res = _run(PERSONALIZE, trace=True)
    assert res["correct"]
    # the CPU trace has no TPU plane: device readers return nothing
    assert "idle_share.personalize" not in res["metrics"]
    assert res["device"]["window_s"] > 0


def _unchanged_state(monkeypatch):
    from repro.serving import bank
    orig = bank.DeltaRing.advance

    def advance(self, state, **kw):
        orig(self, state, **kw)
        return state                       # the window's apply is lost
    monkeypatch.setattr(bank.DeltaRing, "advance", advance)


def _half_batch(monkeypatch):
    from repro.serving import bank
    orig = bank.admission_weights

    def weights(capacity, rows, **kw):
        kept = rows[:max(len(rows) // 2, 1)]
        return orig(capacity, kept, **dict(kw, count=len(kept)))
    monkeypatch.setattr(bank, "admission_weights", weights)


def _altered_head(monkeypatch):
    from repro.core import quant
    orig = quant.QuantizedHeads.row

    def row(self, i):
        head = orig(self, i)
        return jax.tree.map(lambda x: x + 1e-3, head)
    monkeypatch.setattr(quant.QuantizedHeads, "row", row)


def _nan_head(monkeypatch):
    from repro.core import quant
    orig = quant.QuantizedHeads.row

    def row(self, i):
        return jax.tree.map(lambda x: x * float("nan"), orig(self, i))
    monkeypatch.setattr(quant.QuantizedHeads, "row", row)


def _permuted_heads(monkeypatch):
    from repro.serving import server
    orig = server._row_of

    def row_of(handle, row):               # each user gets the next's head
        return orig(handle, (row + 1) % handle.k)
    monkeypatch.setattr(server, "_row_of", row_of)


def _negated_head(monkeypatch):
    from repro.core import quant
    orig = quant.QuantizedHeads.row

    def row(self, i):                      # snapshot + delta: sign flipped
        return jax.tree.map(lambda h, w: 2 * w - h, orig(self, i),
                            self.snapshot)
    monkeypatch.setattr(quant.QuantizedHeads, "row", row)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_head, _nan_head,
                                   _permuted_heads, _negated_head])
def test_broken_personalize_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    assert not _run(PERSONALIZE)["correct"]


def test_altered_token_is_not_correct(monkeypatch):
    from repro.launch import serve
    orig = serve._decode_personalized

    def decode(*a, **kw):
        toks = orig(*a, **kw)
        return toks.at[:, -1].set((toks[:, -1] + 1) % 512)
    monkeypatch.setattr(serve, "_decode_personalized", decode)
    assert not _run(DECODE)["correct"]


def test_control_fails_the_personalize_limits():
    spec = smoke.smoke_spec(PERSONALIZE)
    limits = spec["config"]["limits"]

    def hook(drv):
        drv.setup = lambda orig=drv.setup: (orig(), setattr(
            drv, "captured", list(drv.reference(ssm_lm.fp8))))[0]
    res = _run(PERSONALIZE, hook=hook, spec=spec)
    assert not res["correct"]
    assert any(c["value"] > limits[k] for k, c in res["checks"].items())


def test_control_fails_the_decode_limit():
    spec = smoke.smoke_spec(DECODE)
    mod = run.load_module(f"{run.ROOT}/bench/drivers/decode.py", "dec")
    drv = mod.Driver(spec, SEED)
    drv.setup()
    drv.window(0.3)
    drv.release()
    got = drv.readings(control=True)
    assert got["control"] > spec["config"]["limits"]["logit_gap"]
    assert got["program"] <= spec["config"]["limits"]["logit_gap"]


def test_new_cell_config_mix_and_metric_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(run.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = run.load_json(run.ROOT + "/BENCHMARK.json")
    conf = json.loads((root / "bench/configs/mamba2-130m.json").read_text())
    conf["serving"]["windows"] = 2
    (root / "bench/configs/mamba2-130m-w2.json").write_text(json.dumps(conf))
    mix = json.loads(
        (root / "bench/traffic/personalize_closed.json").read_text())
    mix["concurrency"] = 2
    (root / "bench/traffic/personalize_pairs.json").write_text(
        json.dumps(mix))
    (root / "bench/metrics/windows_served.py").write_text(
        "def read(data):\n    return float(data['window']['windows'])\n")
    bench["configs"].append(dict(bench["configs"][0], name="mamba2-130m-w2",
                                 file="bench/configs/mamba2-130m-w2.json"))
    bench["workloads"].append({"name": "w2.pairs", "config": "mamba2-130m-w2",
                               "traffic": "personalize_pairs", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and PERSONALIZE in m["workloads"]:
            m["workloads"].append("w2.pairs")
    bench["per_layer"].append({"name": "windows_served", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "whole step",
                               "moves": "personalize_req_per_s",
                               "workloads": ["w2.pairs"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = smoke.smoke_spec("w2.pairs", root=str(root))
    assert spec["config"]["serving"]["windows"] == 2
    assert spec["traffic"]["concurrency"] == 2
    assert [m["name"] for m in spec["per_layer"]] == ["windows_served"]
    spec["root"] = str(root)
    res = run.run_cell(spec, SEED, 0.5, True, require_chip=False)
    assert res["correct"], res["checks"]
    assert res["metrics"]["windows_served"]["value"] >= 1


def test_no_accelerator_means_no_result(capsys):
    rc = run.main(["--workload", PERSONALIZE, "--seed", str(SEED),
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "needs 1 TPU chip" in out.err
