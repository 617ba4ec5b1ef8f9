"""The benchmark's parts on the CPU: discovery by name, the load
generator's clock, the reference, the trace reduction, the roofline
arithmetic and the refusal to run off the chip."""
from __future__ import annotations

import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from conftest import BENCH, ROOT
from limsbench import (cell, check, loadgen, reference, roofline, spec,
                       tracereduce, traffic)

FIXTURE = BENCH / "tests" / "fixtures"


def _run_bench(cwd: Path, *args: str) -> subprocess.CompletedProcess:
    env = {"PATH": "/usr/bin:/bin:/usr/local/bin", "JAX_PLATFORMS": "cpu",
           "HOME": str(cwd), "TMPDIR": str(cwd)}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gm32-knn10-closed",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.strip().splitlines()[-1:]:
        try:
            json.loads(line)
            return False
        except ValueError:
            pass
    return True


def test_run_refuses_without_a_tpu(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    (tmp_path / "src").symlink_to(ROOT / "src")
    p = _run_bench(tmp_path)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert _no_result(p.stdout)


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _run_bench(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)


def test_benchmark_names_files_that_exist():
    spec_ = spec.benchmark()
    for c in spec_["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg["reduced"])
        assert (BENCH / "generators" /
                f"{cfg['generator']['name']}.py").exists()
    for w in spec_["workloads"]:
        c = spec.load_cell(w["name"])
        assert c.end_to_end and c.per_layer
        assert "setup_s" in {m["name"] for m in c.end_to_end}
    for m in spec_["per_layer"]:
        assert hasattr(spec.load_module("metrics", m["name"]), "read")


@pytest.mark.parametrize("kind", ["config", "traffic", "metric", "generator"])
def test_new_file_is_found_by_name(tmp_path, kind):
    """A configuration, a traffic mix, a generator or a per-layer
    metric joins by adding its file and its entry; no existing file of
    the harness changes."""
    from conftest import make_root
    root = make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*.py")}
    b = json.loads((root / "BENCHMARK.json").read_text())
    if kind == "config":
        cfg = json.loads((root / "bench/configs/tiny.json").read_text())
        cfg.update(name="tiny2", n=2000)
        (root / "bench/configs/tiny2.json").write_text(json.dumps(cfg))
        b["workloads"].append({"name": "new", "config": "tiny2",
                               "traffic": "tiny-open", "chips": 1,
                               "why": "t"})
    elif kind == "traffic":
        tr = json.loads((root / "bench/traffic/tiny-open.json").read_text())
        tr["rate"] = 7.0
        (root / "bench/traffic/slow.json").write_text(json.dumps(tr))
        b["workloads"].append({"name": "new", "config": "tiny",
                               "traffic": "slow", "chips": 1, "why": "t"})
    elif kind == "metric":
        (root / "bench/metrics/frontend.batches.py").write_text(
            "def read(ctx):\n    return ctx['frontend']['batches']\n")
        b["per_layer"].append({"name": "frontend.batches", "unit": "batches",
                               "better": "higher", "source":
                               "program_counter", "layer": "frontend",
                               "moves": "qps"})
        b["workloads"].append({"name": "new", "config": "tiny",
                               "traffic": "tiny-closed", "chips": 1,
                               "why": "t"})
    else:
        (root / "bench/generators/uniform.py").write_text(
            "import numpy as np\n\ndef generate(n, d, seed):\n"
            "    return np.random.default_rng(seed).uniform(size=(n, d))\n")
        cfg = json.loads((root / "bench/configs/tiny.json").read_text())
        cfg.update(name="unif", generator={"name": "uniform"})
        (root / "bench/configs/unif.json").write_text(json.dumps(cfg))
        b["workloads"].append({"name": "new", "config": "unif",
                               "traffic": "tiny-open", "chips": 1,
                               "why": "t"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    c = spec.load_cell("new", str(root))
    X = traffic.corpus(c.config, str(root))
    assert X.shape == (c.config["n"], c.config["d"])
    if kind == "traffic":
        assert c.traffic["rate"] == 7.0
    if kind == "metric":
        names = [m["name"] for m in c.per_layer]
        assert "frontend.batches" in names
        mod = spec.load_module("metrics", "frontend.batches", str(root))
        assert mod.read({"frontend": {"batches": 3}}) == 3
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_open_loop_times_each_request_from_its_due_instant():
    """One worker and a server that takes 50 ms: the fourth request,
    due at 0.03 s, is sent only after three others are served, and its
    latency counts that wait."""
    def submit(req):
        time.sleep(0.05)
        return req

    due = np.array([0.0, 0.01, 0.02, 0.03])
    t0, recs = loadgen.open_loop(submit, list(range(4)), due, workers=1)
    lat = loadgen.latencies_ms(recs)
    late = loadgen.late_ms(recs)
    assert [r.due - t0 for r in recs] == pytest.approx(due.tolist())
    assert lat[3] >= 4 * 50 - 30 - 5
    assert late[3] >= 3 * 50 - 30 - 5
    assert lat[0] < 60 and late[0] < 10


def test_open_loop_counts_a_shed_request_as_failed():
    def submit(req):
        if req == 1:
            raise loadgen.Overload()
        return req

    _, recs = loadgen.open_loop(submit, [0, 1, 2], np.zeros(3), workers=3)
    assert [r.shed for r in recs] == [False, True, False]
    assert len(loadgen.latencies_ms(recs)) == 2


def test_closed_loop_keeps_every_client_busy_for_the_window():
    served = []

    def submit(req):
        time.sleep(0.01)
        served.append(req)
        return req

    t0, recs = loadgen.closed_loop(submit, list(range(100)), 4, 0.3)
    assert len(recs) == len(served)
    assert 4 * 20 <= len(recs) <= 4 * 31
    assert all(r.done > r.due for r in recs)


def test_arrivals_give_every_seed_the_same_gaps():
    a = traffic.arrivals(50.0, 10.0, 1)
    b = traffic.arrivals(50.0, 10.0, 2 ** 31 + 12345)
    assert len(a) == len(b) == 500
    assert not np.array_equal(a, b)
    gaps = [np.sort(np.diff(np.concatenate([[0.0], x]))) for x in (a, b)]
    assert np.allclose(*gaps)
    assert 9.5 < a[-1] <= 10.0


def test_reference_equals_linear_scan():
    from repro.baselines.linear_scan import LinearScan
    from repro.core import MetricSpace
    X = traffic.load_module("generators", "gauss_mix").generate(
        5000, 8, seed=3, components=20)
    ls = LinearScan(MetricSpace(X, "l2"))
    Q = traffic.queries(X, 12, 0.003, seed=9)
    for q in Q:
        ids, d = reference.answer(X, "knn", q, 10)
        want_i, want_d, _ = ls.knn_query(q, 10)
        assert np.array_equal(ids, want_i) and np.array_equal(d, want_d)
        r = float(d[-1])
        ids, d = reference.answer(X, "range", q, r)
        want_i, want_d, _ = ls.range_query(q, r)
        want_i, want_d = check.canonical(want_i, want_d)
        assert np.array_equal(ids, want_i) and np.array_equal(d, want_d)


def test_reference_does_not_depend_on_the_block():
    X = np.random.default_rng(0).uniform(size=(70_001, 32))
    q = X[5] + 1e-3
    d = reference.distances(X, q)
    assert np.array_equal(d[69_999:], reference.distances(X[69_999:], q))
    assert np.array_equal(d[:3], reference.distances(X[:3], q))


def test_f32_reference_control_fails_the_check():
    X = traffic.load_module("generators", "gauss_mix").generate(
        4000, 8, seed=4, components=20)
    reqs = [traffic.Request("knn", q, 10)
            for q in traffic.queries(X, 8, 0.003, seed=1)]
    recs = [loadgen.Record(r, 0.0, result=reference.answer(X, "knn", r.q, 10))
            for r in reqs]
    ok, checks, n = check.judge(X, recs, 8, 1, 0)
    assert ok and n == 8
    ok, checks, _ = check.judge(
        X, recs, 8, 1, 0,
        lambda rs: reference.answers(X, rs, dtype=np.float32))
    assert not ok and checks["max_dist_gap"]["value"] > 0


def test_sample_takes_the_longest_answers():
    recs = [loadgen.Record(i, 0.0, result=(np.arange(i % 7), None))
            for i in range(40)]
    picked = check.sample(recs, 8, seed=2)
    assert len(picked) == 8
    assert sum(len(r.result[0]) == 6 for r in picked) >= 2


def test_roofline_worked_example():
    peak = roofline.peaks("TPU v5 lite")
    flops, nbytes = roofline.pdist_cost(64, 1_762_048, 32)
    assert nbytes == pytest.approx(225.5e6 + 451.1e6, rel=1e-3)
    assert 2 * 64 * 1_762_048 * 32 <= flops < 1.1 * 2 * 64 * 1_762_048 * 32
    t, bound = roofline.min_seconds("pdist", [(64, 1_762_048, 32)], peak)
    assert bound == "bytes" and t == pytest.approx(0.826e-3, rel=1e-3)
    assert roofline.share("pdist", [(64, 1_762_048, 32)], 2 * t,
                          peak) == pytest.approx(50.0)
    assert roofline.share("pdist", [], 1.0, peak) is None
    _, rb = roofline.range_filter_cost(64, 1 << 17, 32)
    assert rb == 4 * (64 * 32 + (1 << 17) * 32 + 64) + 64 * (1 << 17)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_trace_reduction_on_a_hand_made_trace():
    ms = 1_000_000
    tr = {"device": {"/device:TPU:0": [
        ["pdist_pallas.1", 0, 2 * ms, "jit_pdist_pallas"],
        ["copy", 1 * ms, 2 * ms, "jit_pdist_pallas"],   # overlaps the kernel
        ["range_filter_pallas", 6 * ms, 2 * ms, "jit_range_filter_pallas"]]},
        "host": [["bench.window", 0, 10 * ms],
                 ["bench.frontend.execute", 0, 9 * ms],
                 ["bench.refine.knn", 3 * ms, 2 * ms]]}
    red = tracereduce.reduce(tr, cell.KERNEL_OPS)
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.005)
    assert red["kernel_s"] == pytest.approx({"pdist": 0.002,
                                             "range_filter": 0.002})
    gaps = dict(red["idle_gaps"])
    assert gaps["bench.refine.knn"] == pytest.approx(0.002)
    assert gaps["bench.frontend.execute"] == pytest.approx(0.002)
    assert gaps[tracereduce.NO_SPAN] == pytest.approx(0.001)
    assert sum(gaps.values()) == pytest.approx(0.005)


def test_trace_reduction_on_a_recorded_chip_trace():
    """A slice of a profiler trace of gm32-knn10-closed recorded on a
    TPU v5e, and the numbers its reduction gave when it was recorded."""
    rec = json.loads((FIXTURE / "trace_gm32_knn10.json").read_text())
    red = tracereduce.reduce(rec["trace"], cell.KERNEL_OPS)
    for key in ("window_s", "busy_s"):
        assert red[key] == pytest.approx(rec["expected"][key], rel=1e-9)
    assert red["kernel_s"] == pytest.approx(rec["expected"]["kernel_s"])
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["kernel_events"]["pdist"] > 0
    gaps = sum(v for _, v in red["idle_gaps"])
    assert gaps <= red["window_s"] - red["busy_s"] + 1e-9


def test_batch_sizes():
    tr = {"loop": "closed", "clients": 128, "frontend": {"max_batch": 64},
          "queries": [{"kind": "range", "selectivity": 1e-4},
                      {"kind": "range", "selectivity": 1e-3}]}
    assert cell.batch_sizes(tr) == [64]
    tr["queries"].append({"kind": "knn", "k": 10})
    assert cell.batch_sizes(tr) == list(range(1, 65))
    assert cell.batch_sizes({"loop": "open", "frontend": {"max_batch": 4},
                             "queries": []}) == [1, 2, 3, 4]


def test_precision_control_lowers_only_highest(monkeypatch):
    """The control's switch: every ``jax.lax.dot_general`` the kernels
    make at HIGHEST runs at DEFAULT instead; other precisions pass."""
    import jax
    seen = []

    def record(*a, precision=None, **kw):
        seen.append(precision)
    monkeypatch.setattr(jax.lax, "dot_general", record)
    control = spec.load_module("", "control")
    control.lower_kernel_precision()
    jax.lax.dot_general(1, 2, precision=jax.lax.Precision.HIGHEST)
    jax.lax.dot_general(1, 2, precision=jax.lax.Precision.HIGH)
    jax.lax.dot_general(1, 2)
    assert seen == [jax.lax.Precision.DEFAULT, jax.lax.Precision.HIGH, None]


def test_benchmark_json_keeps_the_format():
    import re
    b = spec.benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in b[k]]
    assert all(name.match(n) for n in names)
    text = [e["why"] for k in ("configs", "workloads") for e in b[k]]
    text += [m["layer"] for m in b["per_layer"]]
    text += [c["source"] for c in b["configs"]] + b["command"]
    assert all(0 < len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in text)
    metrics = b["end_to_end"] + b["per_layer"]
    assert all(unit.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    e2e = {m["name"] for m in b["end_to_end"]}
    assert all(m["moves"] in e2e for m in b["per_layer"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in b["end_to_end"])
    assert 1 <= b["run_seconds"] <= 51
    assert len(json.dumps(b)) <= 64 * 1024
