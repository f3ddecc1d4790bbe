"""``correct`` at a size a test run holds, on the host CPU: a sound run of
each cell passes, and the control and every planted fault come out not
correct under the limits the configuration files state.

The harness's look for a chip is skipped; everything else of a run goes as
on the chip: set-up, a short window, the reference, the comparison.
"""
import json

import pytest

import harness
from harness import BENCH, load_json, load_module

run = load_module(BENCH / "run.py")
faults = load_module(BENCH / "faults.py")


def tiny(workload):
    """The cell's configuration and traffic, shrunk in depth, width and
    scale so that a CPU runs them in seconds; limits as committed."""
    bench = load_json(harness.ROOT / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}[workload]
    cfg = load_json(BENCH / "configs" / f"{entry['config']}.json")
    tr = load_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    if cfg["driver"] == "sweep":
        cfg.update(num_clients=10, clients_per_round=4, rounds=50,
                   batch_size=8)
        cfg["data"] = dict(cfg["data"], train=600, test=200, dim=16)
    else:
        cfg["model"].update(num_hidden_layers=2, hidden_size=256,
                            num_attention_heads=4, num_key_value_heads=2,
                            head_dim=64, intermediate_size=512,
                            vocab_size=512)
        tr.update(tokens_per_client=1024, distinct_batches=8)
    return bench, entry, cfg, tr


@pytest.fixture
def cpu_cell(monkeypatch):
    """Point the harness at a tiny cell and let it run on the CPU."""
    import jax
    from repro import configs
    from repro.configs.qwen2_0_5b import reduced

    def use(workload):
        parts = tiny(workload)
        monkeypatch.setattr(harness, "cell", lambda name: parts)
        monkeypatch.setattr(harness, "device_gate",
                            lambda jax_, chips, peaks: jax.devices()[:chips])
        monkeypatch.setattr(harness, "configure_cache", lambda: None)
        monkeypatch.setattr(configs, "get_config", lambda name: reduced())
        return parts
    return use


def run_once(capsys, workload, seed=2**40 + 5):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "0.5", "--trace", "0"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


WORKLOADS = ["paper-fig23-exact-k", "qwen2-0.5b-ca-afl"]
CASES = [(w, f) for w in WORKLOADS
         for f in faults.FAULTS[tiny(w)[2]["driver"]]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(cpu_cell, capsys, workload):
    cpu_cell(workload)
    res = run_once(capsys, workload)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert "setup_s" in res["metrics"]
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_is_not_correct(cpu_cell, workload):
    _, _, cfg, tr = cpu_cell(workload)
    drv = load_module(BENCH / "drivers" / f"{cfg['driver']}.py").Driver(
        cfg, tr, 77)
    drv.setup()
    drv.window(0.1)
    drv.release()
    checks = drv.check(control="bfloat16")
    assert not all(run.passes(c) for c in checks), checks


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_not_correct(cpu_cell, capsys, workload, fault):
    _, _, cfg, _ = cpu_cell(workload)
    with faults.FAULTS[cfg["driver"]][fault]():
        res = run_once(capsys, workload)
    assert not res["correct"], res["checks"]


def test_no_result_without_a_chip(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "paper-fig23-exact-k", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_reference_follows_every_selection_rule(cpu_cell):
    """The sweep reference against the program for all five rules of the
    paper's grid, GCA included (whose round does not finish on the chip;
    PERF.md, Open questions)."""
    _, _, cfg, tr = cpu_cell("paper-fig23-exact-k")
    tr = dict(tr, points=[{"label": m, "method": m} for m in
                          ("fedavg", "afl", "gca", "ca_afl")])
    drv = load_module(BENCH / "drivers" / "sweep.py").Driver(cfg, tr, 5)
    drv.setup()
    drv.window(0.1)
    drv.release()
    checks = drv.check()
    assert all(run.passes(c) for c in checks), checks


def test_rounds_compared_stop_at_a_selection_rounding_could_flip():
    import numpy as np

    sweep = load_module(BENCH / "drivers" / "sweep.py")
    want = {"margin": np.ones(3), "lam": np.full((3, 3), 1 / 3),
            "mask": np.array([[1, 0, 0]] * 3),
            "noise": np.array([[0.5, 0.0, 0.0]] * 3)}
    assert sweep.comparable_rounds(want, want["lam"], True, 1e-3) == 3
    # the compared run's λ after round 1 brings client 1 within 0.054 of
    # the scheduled client 0 in round 2 (log 0.32 + 0.5 against log 0.5)
    got = want["lam"].copy()
    got[1] = [0.32, 0.5, 0.18]
    assert sweep.comparable_rounds(want, got, True, 1e-3) == 3
    assert sweep.comparable_rounds(want, got, True, 0.06) == 2
    # past the tie: under this λ client 1 would have been scheduled
    got[1] = [0.3, 0.5, 0.2]
    assert sweep.comparable_rounds(want, got, True, 1e-3) == 2
    # a rule that does not select on λ is cut by the reference's ties only
    assert sweep.comparable_rounds(want, got, False, 0.06) == 3
    tie = dict(want, margin=np.array([1.0, 1e-4, 1.0]))
    assert sweep.comparable_rounds(tie, want["lam"], False, 1e-3) == 1
