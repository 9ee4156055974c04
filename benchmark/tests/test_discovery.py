"""A configuration, a traffic mix, a kind of traffic with a loop of its own
and a per-layer metric that a later PR adds as files are found by name and
run, with no edit to the harness; a mix's statistic is computed as named."""

import json
import shutil
import time

import pytest

from benchmark import run

from .conftest import make_root

#: A later PR's kind: requests arrive every 5 ms whatever the verdicts
#: before them (an open loop), each served in 1 to 20 ms by 4 callers.
OPEN_KIND = '''
import time

from benchmark.traffic import gen


def make(seed, stream, i):
    return {"expect": True, "ms": 1 + (seed + i) % 20}


class Traffic(gen.Kind):
    def item_job(self, stream, i):
        return ("make", (self.seed, stream, i))

    def request(self, raw):
        return raw

    def call(self, req):
        time.sleep(req["ms"] / 1e3)
        return True

    @staticmethod
    def work(raw):
        return {"ms": raw["ms"]}

    def drive(self, next_request, fire, deadline):
        arrivals = [k * self.mix["every_s"] for k in range(100000)]
        gen.open_loop(next_request, fire, deadline, arrivals, self.mix["callers"])
'''


def _later_pr(tmp_path):
    root = make_root(tmp_path)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cfg = json.loads((tmp_path / "benchmark/configs/mainnet_1m.json").read_text())
    cfg["committee_size"] = 2
    cfg["name"] = "other_net"
    (tmp_path / "benchmark/configs/other_net.json").write_text(json.dumps(cfg))
    shutil.copy(tmp_path / "benchmark/traffic/block_sigs.json",
                tmp_path / "benchmark/traffic/block_sigs_busy.json")
    (tmp_path / "benchmark/traffic/gossip.py").write_text(OPEN_KIND)
    (tmp_path / "benchmark/traffic/gossip.json").write_text(json.dumps(
        {"kind": "gossip", "callers": 4, "every_s": 0.005, "warmup_items": 1,
         "pool_items": 500, "trace_requests": 4,
         "report": {"gossip_verify_p95_ms": "latency_p95"}}))
    (tmp_path / "benchmark/metrics/new_layer_ms.block.py").write_text(
        "def read(ctx):\n    return 1.5 * len(ctx['flight'])\n")
    bench["configs"].append({"name": "other_net", "source": "x",
                             "file": "benchmark/configs/other_net.json",
                             "reduced": [], "why": "a later PR's network"})
    bench["workloads"] += [
        {"name": "other_net.busy", "config": "other_net",
         "traffic": "block_sigs_busy", "chips": 1, "why": "a later PR's cell"},
        {"name": "mainnet_1m.gossip", "config": "mainnet_1m", "traffic": "gossip",
         "chips": 1, "why": "a later PR's open loop"}]
    bench["end_to_end"][1]["workloads"].append("other_net.busy")
    bench["end_to_end"].append({"name": "gossip_verify_p95_ms", "unit": "ms",
                                "better": "lower", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["mainnet_1m.gossip"]})
    bench["per_layer"].append({"name": "new_layer_ms.block", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "host marshal", "moves": "block_verify_p50_ms",
                               "workloads": ["other_net.busy"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_new_files_are_found_by_name(tmp_path):
    root = _later_pr(tmp_path)
    bench2, cell, cfg2, mix = run.load_cell("other_net.busy", root)
    assert cfg2["committee_size"] == 2 and mix["kind"] == "block_sigs"
    names = [m["name"] for m in run.per_layer_for(bench2, cell)]
    assert names == ["new_layer_ms.block"]
    assert run.metric_reader("new_layer_ms.block", root)({"flight": [1, 2]}) == 3.0


def test_a_new_kind_with_an_open_loop_runs(tmp_path):
    """The later PR's kind, its items made in a worker process from the
    file, offered by its own open loop, reported as its p95."""
    root = _later_pr(tmp_path)
    info, result = run.run_cell("mainnet_1m.gossip", 7, 2.0, False,
                                require_tpu=False, root=root, workers=1,
                                t0=time.perf_counter())
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"setup_s", "gossip_verify_p95_ms"}
    p95 = result["metrics"]["gossip_verify_p95_ms"]["value"]
    # ~400 requests of 1..20 ms: the p95 lies near the top, above the p90
    assert info["requests"] > 200 and not info["pool_outrun"]
    assert info["p90_ms"] <= p95 and 18 <= p95 < 60


def test_statistics_over_all_requests():
    w = {"latency_ms": [float(v) for v in range(1, 101)], "completed": 100,
         "window_s": 4.0}
    assert run.STATS["latency_p50"](w) == 50.5
    assert run.STATS["latency_p95"](w) == 95.0
    assert run.STATS["latency_p90"](w) == 90.0
    assert run.STATS["requests_per_s"](w) == 25.0


def test_a_mix_must_name_a_known_statistic():
    e2e = [{"name": "setup_s"}, {"name": "x_p95_ms"}]
    assert run.reported({"report": {"x_p95_ms": "latency_p95"}}, e2e) == \
        {"x_p95_ms": "latency_p95"}
    with pytest.raises(ValueError, match="not a statistic"):
        run.reported({"report": {"x_p95_ms": "p95"}}, e2e)
    with pytest.raises(ValueError, match="does not report"):
        run.reported({"report": {}}, e2e)
