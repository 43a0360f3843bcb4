"""BENCHMARK.json resolves by name: every cell to its configuration,
traffic and metric files, every per-layer metric to a reader, every name
and unit within the allowed characters.  This is the guard that lets a
later change add a cell, a mix or a metric as data alone."""
import copy
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from bench import spec  # noqa: E402


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_benchmark_file_has_no_problems(bench):
    assert spec.problems(bench) == []


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][1].startswith(bench["paths"][0] + "/")
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert (ROOT / p).is_dir()


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  spec.load_benchmark()["workloads"]])
def test_every_cell_resolves(bench, cell):
    r = spec.resolve(bench, cell)
    m, mix = r["config"], r["traffic"]
    assert m["name"] == r["workload"]["config"]
    names = [x["name"] for x in r["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2
    assert r["per_layer"]
    for pm in r["per_layer"]:
        assert pm["moves"] in names
        assert callable(spec.metric_reader(pm["name"]))
    src = r["generator"].make(mix, 1, m, r["load"])
    assert src.prompt_lengths
    # every prompt and answer fits one sequence's blocks
    e = m["engine"]
    cap = e["max_blocks_per_seq"] * m["assumed"]["page_size"]
    out_max = mix["output_tokens"]["max"]
    assert max(src.prompt_lengths) + out_max <= cap


@pytest.mark.parametrize("name", [c["name"] for c in
                                  spec.load_benchmark()["configs"]])
def test_config_file_is_what_the_program_runs(bench, name):
    from bench.run import program_config
    m = spec.config_file(bench, name)
    entry = spec.config_entry(bench, name)
    assert m["name"] == name
    assert sorted(m["reduced"]) == sorted(entry["reduced"])
    cfg = program_config(m)       # raises on any number that differs
    assert cfg.num_layers == m["num_hidden_layers"]


def test_naming_rules_catch_bad_entries(bench):
    bad = copy.deepcopy(bench)
    bad["end_to_end"][0]["name"] = "tokens per second"
    bad["per_layer"][0]["unit"] = "milli seconds"
    bad["per_layer"][1]["moves"] = "no_such_metric"
    bad["workloads"][0]["traffic"] = "no_such_mix"
    found = "\n".join(spec.problems(bad))
    assert "bad name 'tokens per second'" in found
    assert "bad unit 'milli seconds'" in found
    assert "moves unknown no_such_metric" in found
    assert "no traffic file no_such_mix.json" in found


def test_a_new_cell_is_data_only(bench):
    """A cell added as an entry over existing files resolves, and its
    metrics follow the `workloads` lists."""
    base = copy.deepcopy(bench)
    used = {(w["config"], w["traffic"]) for w in base["workloads"]}
    mixes = sorted(p.stem for p in (spec.BENCH_DIR / "traffic").glob("*.json")
                   if not spec.traffic_generator(
                       spec.load_json(p)["kind"]).CELL_KEYS)
    free = [(c["name"], t) for c in base["configs"] for t in mixes
            if (c["name"], t) not in used]
    if free:
        config, traffic = free[0]
        cell = {"name": f"{config}.{traffic}", "config": config,
                "traffic": traffic, "chips": 1, "why": "test"}
    else:          # every pair is a cell: take one out and put it back
        cell = base["workloads"].pop()
        for m in base["end_to_end"] + base["per_layer"]:
            if cell["name"] in m.get("workloads", []):
                m["workloads"].remove(cell["name"])
    more = copy.deepcopy(base)
    more["workloads"].append(dict(cell))
    # every cell reports at least one per-layer metric
    assert f"{cell['name']}: no per-layer metric" in spec.problems(more)
    step = spec.find_metric(more, "decode_step_ms")
    step["workloads"].append(cell["name"])
    moves = spec.find_metric(more, step["moves"])
    if "workloads" in moves:
        # the metric it moves has to be reported there too
        assert "does not report" in "\n".join(spec.problems(more))
        moves["workloads"].append(cell["name"])
    assert spec.problems(more) == []
    r = spec.resolve(more, cell["name"])
    assert [m["name"] for m in r["end_to_end"]] == [
        m["name"] for m in more["end_to_end"]
        if "workloads" not in m or cell["name"] in m["workloads"]]
    assert [m["name"] for m in r["per_layer"]] == ["decode_step_ms"]
    # the pair (config, traffic) must stay unique
    more["workloads"].append(dict(cell, name="copy"))
    assert any("repeats" in p for p in spec.problems(more))


def test_an_open_loop_cell_needs_its_own_rate(bench):
    """The rate an open loop offers is the cell's (``cells/<cell>.json``),
    never the mix's: a cell without that file fails the guard and does
    not resolve, and every open-loop cell has its rate."""
    open_mixes = [p.stem for p in (spec.BENCH_DIR / "traffic").glob("*.json")
                  if spec.load_json(p)["kind"] == "open_loop"]
    assert open_mixes
    for w in bench["workloads"]:
        if w["traffic"] in open_mixes:
            assert spec.resolve(bench, w["name"])["load"]["rate_per_s"] > 0
    more = copy.deepcopy(bench)
    more["workloads"].append({"name": "no-rate.cell", "chips": 1,
                              "config": bench["configs"][0]["name"],
                              "traffic": open_mixes[0], "why": "test"})
    assert any("cells/no-rate.cell.json lacks ['rate_per_s']" in p
               for p in spec.problems(more))
    with pytest.raises(KeyError, match="rate_per_s"):
        spec.resolve(more, "no-rate.cell")
