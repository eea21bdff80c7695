"""The traffic generator, and finding a cell's parts by name."""

import json
import shutil

import numpy as np
import pytest

from perfbench import harness, traffic

STEADY = {"arrivals": "poisson", "rate_of_knee": 0.8,
          "queries": {"kind": "distinct"}}


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 7])
def test_every_seed_gets_the_same_work(seed):
    a = traffic.schedule(STEADY, 80.0, 20.0, np.random.default_rng(0))
    b = traffic.schedule(STEADY, 80.0, 20.0, np.random.default_rng(seed))
    assert a.due_s.size == b.due_s.size == 1600
    # the m gaps, the last one running to the window's end
    gaps = [np.sort(np.append(np.diff(s.due_s), 20.0 - s.due_s[-1]))
            for s in (a, b)]
    assert np.allclose(gaps[0], gaps[1], atol=1e-9)
    assert not np.array_equal(a.due_s, b.due_s)
    assert a.due_s.min() == 0.0 and a.due_s.max() < 20.0
    assert np.all(np.diff(a.due_s) >= 0)
    assert sorted(a.query) == list(range(1600))     # every query distinct


def test_gaps_are_exponential():
    s = traffic.schedule(STEADY, 100.0, 50.0, np.random.default_rng(3))
    gaps = np.diff(s.due_s)
    assert gaps.mean() == pytest.approx(0.01, rel=0.01)
    assert gaps.std() == pytest.approx(0.01, rel=0.1)   # exponential: sd = mean


def test_bursts_keep_the_count_and_mean():
    mix = {**STEADY, "burst": {"factor": 3.0, "period_s": 2.0, "duty": 0.25}}
    s = traffic.schedule(mix, 40.0, 20.0, np.random.default_rng(5))
    assert s.due_s.size == 800
    phase = np.mod(s.due_s, 2.0)
    on = np.sum(phase < 0.5)
    assert on / s.due_s.size == pytest.approx(0.75, abs=0.05)  # 3 x 0.25


def test_zipf_repeats_a_pool():
    mix = {**STEADY, "queries": {"kind": "zipf", "pool": 500,
                                 "exponent": 1.0}}
    s = traffic.schedule(mix, 50.0, 20.0, np.random.default_rng(9))
    counts = np.bincount(s.query, minlength=500)
    assert s.query.max() < 500
    assert counts.max() > 50 and (counts == 0).any()


def test_queries_are_seeded_f32():
    a = traffic.make_queries(4, 768, np.random.default_rng(11))
    b = traffic.make_queries(4, 768, np.random.default_rng(11))
    assert a.dtype == np.float32 and a.shape == (4, 768)
    assert np.array_equal(a, b)
    assert np.linalg.norm(a, axis=1) == pytest.approx(1.0, abs=0.1)


def test_cell_parts_are_found_by_name(tmp_path):
    """A configuration, traffic mix and per-layer metric added as files
    and BENCHMARK.json entries alone are found."""
    root = tmp_path
    shutil.copytree(harness.ROOT / "perfbench", root / "perfbench")
    bench = harness.benchmark()
    cfg = json.loads((harness.ROOT / bench["configs"][0]["file"])
                     .read_text())
    (root / "perfbench" / "configs" / "new-deploy.json").write_text(
        json.dumps({**cfg, "rows": 1234}))
    (root / "perfbench" / "traffic" / "over.json").write_text(
        json.dumps({**STEADY, "rate_of_knee": 1.2}))
    (root / "perfbench" / "metrics" / "new.metric_ms.py").write_text(
        "def read(layers):\n    return 41.5\n")
    bench["configs"].append({"name": "new-deploy", "source": "x",
                             "file": "perfbench/configs/new-deploy.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new.over", "config": "new-deploy",
                               "traffic": "over", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new.metric_ms", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "x", "moves": "latency_p50_ms",
                               "workloads": ["new.over"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    got = harness.benchmark(root)
    w = harness.cell(got, "new.over")
    assert harness.config(got, w["config"], root)["rows"] == 1234
    assert harness.traffic_mix(w["traffic"], root)["rate_of_knee"] == 1.2
    names = [m["name"] for m in harness.metrics_of(got, "per_layer",
                                                   "new.over")]
    assert "new.metric_ms" in names
    assert harness.reader("new.metric_ms", root).read(None) == 41.5
    assert harness.builder(cfg["builder"]).build
    with pytest.raises(KeyError):
        harness.cell(got, "no.such")
