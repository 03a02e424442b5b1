"""The harness is driven by data: every cell resolves its configuration
and mix by name, every metric its reader, and every name and unit keeps to
the characters the benchmark allows."""
import re

import pytest

from benchmarks.chip import spec, traffic

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_each_workload_resolves_its_config_and_mix(w):
    cell = spec.cell(w["name"])
    assert cell["config"]["name"] == w["config"]
    assert w["config"] in {c["name"] for c in BENCH["configs"]}
    assert cell["mix"]["loop"] == "closed"
    assert w["chips"] == 1
    assert cell["end_to_end"] and cell["per_layer"]
    assert "setup_s" in {m["name"] for m in cell["end_to_end"]}


def test_each_per_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        for w in m.get("workloads", [x["name"] for x in BENCH["workloads"]]):
            reported = {x["name"] for x in spec.cell(w)["end_to_end"]}
            assert m["moves"] in reported, (m["name"], w)
        assert callable(spec.reader(m["name"]).read)


def test_names_and_units_use_the_allowed_characters():
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    all_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(all_names) == len(set(all_names))


def test_bounds_and_run_length_keep_to_their_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_seed_sends_the_same_sizes_with_its_own_tokens():
    mix = spec.traffic_file("chat")
    plans = traffic.client_plans(mix)
    for c, plan in enumerate(plans):
        assert plan[0] == tuple(mix["openers"][c])
        assert plan[1:] == traffic.streams(mix)[c]
    a, b = (traffic.prompt_tokens(s, 1, 2, 50, 102400)
            for s in (7, 2**31 + 11))
    assert a != b and len(a) == 50 and max(a + b) < 102400
    s, n = mix["stratum"], mix["requests_per_client"]
    m = n // s
    streams = traffic.streams(mix)
    for key, col in (("prompt_tokens", 0), ("output_tokens", 1)):
        grid = traffic.grid(mix[key], n)
        bands = [grid[b * m:(b + 1) * m] for b in range(s)]
        for st in streams:           # each stream runs through the grid
            assert sorted(x[col] for x in st) == grid
        for k in range(n):           # the clients' k-th requests span bands
            got = sorted(st[k][col] for st in streams)
            assert all(bands[i][0] <= got[i] <= bands[i][-1]
                       for i in range(s))
    assert min(traffic.grid(mix["prompt_tokens"], n)) >= 4
    assert traffic.max_tokens(mix) == 1024 + 1024


def test_the_grid_keeps_the_sources_means():
    mix = spec.traffic_file("chat")
    for key in ("prompt_tokens", "output_tokens"):
        dist = mix[key]
        got = sum(traffic.grid(dist, 4096)) / 4096
        assert abs(got - dist["mean"]) < 0.06 * dist["mean"], (key, got)


def test_openers_end_a_chunk_on_every_bucket_and_start_decoding_in_turn():
    mix = spec.traffic_file("chat")
    from repro.serving.engine import bucket_size
    for chunk in (4, 5, 8):          # the prefill chunks the cells run
        last = {bucket_size((p - 1) % chunk or chunk, chunk)
                for p, _ in mix["openers"]}
        assert last == {bucket_size(n, chunk) for n in range(1, chunk + 1)}
        chunks = [-(-(p - 1) // chunk) for p, _ in mix["openers"]]
        assert chunks == sorted(set(chunks))
