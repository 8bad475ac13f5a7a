"""BENCHMARK.json and every file it names, and each cell driven end to end
at a tiny size on the CPU: sound runs pass the check; the float32 control
and runs with the solve broken underneath fail it."""
import copy
import json
import os
import re
import time

import numpy as np
import pytest

from bench import control, harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
MAN = harness.manifest()


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 \
        and "\n" not in text and "\t" not in text


def test_manifest_follows_the_contract():
    assert os.path.getsize(harness.MANIFEST) <= 64 * 1024
    assert set(MAN) == KEYS["top"]
    assert MAN["paths"] == ["bench"] and MAN["command"][1] == "bench/run.py"
    assert 1 <= MAN["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MAN[group]:
            assert set(entry) - {"workloads"} == KEYS[group], entry
            assert NAME.match(entry["name"]), entry["name"]
            names.append(entry["name"])
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert _line(entry[key]), (entry["name"], key)
            if "unit" in entry:
                assert UNIT.match(entry["unit"]) and \
                    entry["better"] in ("lower", "higher")
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in MAN["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in MAN["workloads"]}
    for m in MAN["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_every_named_file_exists_and_matches():
    configs = {c["name"]: c for c in MAN["configs"]}
    for c in MAN["configs"]:
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
    pairs = set()
    for w in MAN["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        _, config, traffic = harness.cell(w["name"])
        assert config["chips"] == w["chips"]
        assert set(traffic) == {"items_per_job", "chains"}
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert os.path.isfile(os.path.join(harness.BENCH, "metrics",
                                           m["name"] + ".py"))


def _tiny(name):
    """The cell at a tiny size: a 16 x 16 grid, jobs of 16 items on 4
    chains."""
    entry, config, _ = harness.cell(name)
    config = copy.deepcopy(config)
    config["family_params"].update(nx=16, ny=16)
    return entry, config, {"items_per_job": 16, "chains": 4}


def _run(name, seconds=0.5, traced=False):
    entry, config, traffic = _tiny(name)
    rec = harness.run(config, traffic, 2**33 + 7, seconds, traced,
                      time.perf_counter())
    rec["device_kind"] = "TPU v5 lite"
    return entry, rec


def _main(monkeypatch, capsys, name):
    """`run.py`'s whole run of the cell at its tiny size, with the look for
    a chip skipped and the compile cache left alone: its result line."""
    import sys

    import jax

    from bench import run
    from repro import compile_cache

    monkeypatch.setattr(run, "chips", lambda entry: jax.devices())
    tiny = _tiny(name)
    monkeypatch.setattr(harness, "cell", lambda *_: tiny)
    monkeypatch.setattr(compile_cache, "enable", lambda root: None)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(sys, "path", list(sys.path))
    rc = run.main(["--workload", name, "--seed", str(2**33 + 7),
                   "--seconds", "0.5", "--trace", "0"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


CELLS = [w["name"] for w in MAN["workloads"]]
FIRST = CELLS[0]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct_at_a_tiny_size(name):
    entry, rec = _run(name)
    assert rec["compiles"]["window"] == 0
    assert rec["labels_due"] == rec["labels_ok"] == rec["labels"] > 0
    checks = harness.check(rec)
    assert harness.passed(checks), checks
    for m in MAN["end_to_end"]:
        assert harness.read_metric(m["name"], rec) > 0


def test_result_line_holds_the_metrics_and_checks_last(monkeypatch, capsys):
    line = _main(monkeypatch, capsys, FIRST)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in MAN["end_to_end"]}
    assert list(line)[-1] == "checks"
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]


def test_traced_run_reports_its_per_layer_metrics():
    from bench import trace as xtrace

    entry, rec = _run(FIRST, seconds=2.0, traced=True)
    tr = xtrace.read(xtrace.find(harness.TRACE_DIR))
    rec["trace_summary"] = xtrace.summarize(tr)
    got = {m["name"]: harness.read_metric(m["name"], rec, tr)
           for m in harness.metric_names(MAN, entry, "per_layer")}
    assert 0 < got["lockstep.eff"] <= 100
    assert 0 <= got["pipeline.host_share"] < 100
    assert got["solver.iters_per_label"] > 0
    assert got["solver.syncs_per_label"] > 0
    # the CPU has no device plane: nothing to read, so no idle share at all
    assert got["device.idle"] is None


def test_float32_control_fails_the_check():
    _, rec = _run(FIRST)
    jobs, ems = rec["job_kind"], rec["emitted"]
    assert jobs.check(ems)["max_res"]["value"] <= jobs.limit
    ctrl = jobs.check(control.control_labels(jobs, ems, np.float32))
    assert ctrl["max_res"]["value"] > ctrl["max_res"]["limit"]


def _broken(kind):
    """solve_batch with its answers broken where they are produced."""
    import dataclasses

    from repro.solvers.batched import BatchedGCRODRSolver

    solve = BatchedGCRODRSolver.solve_batch

    def broken(self, ops, b, padded_rows=None):
        xs, stats = solve(self, ops, b, padded_rows=padded_rows)
        xs = np.array(xs, copy=True)
        half = len(xs) // 2
        if kind == "altered":          # one answer changed
            xs[0] *= 1.0 + 1e-5
        elif kind == "half":           # half of the batch left out
            xs[half:] = 0.0
        elif kind == "unchanged":      # the step returns its state
            xs = np.array(b, copy=True)
        elif kind == "flagged":        # half given up on and flagged failed
            xs[half:] = 0.0
            stats = stats[:half] + [dataclasses.replace(s, converged=False)
                                    for s in stats[half:]]
        return xs, stats

    return broken


@pytest.mark.parametrize("kind", ["altered", "half", "unchanged", "flagged"])
def test_broken_solve_is_not_correct(monkeypatch, capsys, kind):
    from repro.core.skr import SteadyWork
    from repro.solvers.batched import BatchedGCRODRSolver

    monkeypatch.setattr(BatchedGCRODRSolver, "solve_batch", _broken(kind))
    # no containment re-solve: the labels given up on stay flagged failed
    monkeypatch.setattr(SteadyWork, "requeue_quarantined", lambda self: None)
    line = _main(monkeypatch, capsys, FIRST)
    assert line["correct"] is False and line["failed"] > 0
    if kind == "flagged":
        assert line["checks"]["failed"]["value"] > 0
        assert line["checks"]["max_res"]["value"] <= \
            line["checks"]["max_res"]["limit"]


def test_labels_left_out_are_not_correct(monkeypatch, capsys):
    """A job that emits fewer labels than its rows solved: each chain's
    last label dropped from its result."""
    import dataclasses

    from repro.core.skr import SteadyWork

    chunk = SteadyWork.chunk_result

    def short(self, w):
        res = chunk(self, w)
        return dataclasses.replace(
            res, inputs=res.inputs[:-1], solutions=res.solutions[:-1],
            order=res.order[:-1], label_ok=res.label_ok[:-1])

    monkeypatch.setattr(SteadyWork, "chunk_result", short)
    line = _main(monkeypatch, capsys, FIRST)
    assert line["correct"] is False
    assert line["checks"]["missing"]["value"] > 0
