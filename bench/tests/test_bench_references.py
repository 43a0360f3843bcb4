"""Each configuration's architecture resolves by name to its module in
``bench/references/``, and the harness takes everything it knows of the
mathematics from there.

- Pins: the weights, the reference's gaps and the cost numbers the
  decoder module gives are bit for bit those the harness gave before its
  architecture code moved there (``fixtures/decoder_parity.json``, made
  by the harness of that commit with the inputs below).
- The resolver: ``spec.problems`` names a configuration without a module,
  a module that is missing, and one that lacks a function.
- New files only: an architecture module that exists only under a new
  name in ``sys.modules`` carries a whole run of a tiny CPU cell.
- No harness file outside ``bench/references/`` reads an architecture's
  keys from a configuration.
"""
import ast
import copy
import hashlib
import json
import pathlib
import sys
import types

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import cost, run, spec, weights  # noqa: E402
from bench.references import decoder  # noqa: E402

FIX = spec.BENCH_DIR / "tests" / "fixtures"
PINS = json.loads((FIX / "decoder_parity.json").read_text())
SEEDS = [7, 2**31 + 5]
TINY = ["tiny-dense", "tiny-moe"]
CONFIG_FILES = {p.stem: p for p in
                sorted((spec.BENCH_DIR / "configs").glob("*.json"))
                + [FIX / "tiny-dense.json", FIX / "tiny-moe.json"]}
#: a fixed mix of drained rows, one of every opcode
ROWS = {"fpm_copy": 3, "psm_copy": 2, "baseline_copy": 1, "zero_init": 5,
        "cross_pool_copy": 4, "and": 1, "or": 2, "not": 7}
#: configuration keys only an architecture's module may read
ARCH_KEYS = ("family", "n_routed_experts", "num_key_value_heads",
             "moe_intermediate_size", "intermediate_size", "capacity_factor")


def digest(x):
    a = np.asarray(x)
    return [str(a.dtype), list(a.shape),
            hashlib.sha256(a.tobytes()).hexdigest()]


def fixed_requests(vocab):
    rng = np.random.default_rng(20261018)
    prompts, served = [], []
    for n, s in ((70, 6), (190, 11), (33, 3)):
        prompts.append(rng.integers(2, vocab, size=n).astype(np.int32))
        served.append([int(t) for t in rng.integers(2, vocab, size=s)])
    return prompts, served


# -- pins --------------------------------------------------------------------
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", TINY)
def test_weights_match_the_pins(name, seed):
    m = spec.load_json(FIX / f"{name}.json")
    p = weights.make_params(m, seed)
    got = {jax.tree_util.keystr(k): digest(v) for k, v in
           jax.tree_util.tree_flatten_with_path(p)[0]}
    for lay in range(m["num_hidden_layers"]):
        for path, v in weights.layer(m, seed, lay).items():
            got[f"layer {lay} {path}"] = digest(v)
    for path, v in weights.top(m, seed).items():
        got[f"top {path}"] = digest(v)
    assert got == PINS["weights"][f"{name} {seed}"]


@pytest.mark.parametrize("name", TINY)
def test_logit_gaps_match_the_pins(name):
    m = spec.load_json(FIX / f"{name}.json")
    prompts, served = fixed_requests(m["vocab_size"])
    gaps = spec.reference(m).logit_gaps(m, 11, prompts, served,
                                        control=True)
    pins = PINS["gaps"][name]
    assert len(gaps) == len(pins)
    for g, pin in zip(gaps, pins):
        assert set(g) == set(pin) == {"gap", "margin", "gap_control"}
        for k in pin:
            assert np.array_equal(np.asarray(g[k], np.float32),
                                  np.asarray(pin[k], np.float32)), k


@pytest.mark.parametrize("name", sorted(CONFIG_FILES))
def test_cost_matches_the_pins(name):
    m = spec.load_json(CONFIG_FILES[name])
    ref = spec.reference(m)
    got = {"matmul_weights_per_token": ref.matmul_weights_per_token(m),
           "attn_flops_per_pair": ref.attn_flops_per_pair(m),
           "kv_block_bytes": ref.kv_block_bytes(m, 64),
           "drain_bytes": cost.drain_bytes(m, 64, ROWS),
           "decode_flops": cost.decode_flops(m, 1000),
           "prefill_flops": cost.prefill_flops(m, 700)}
    assert got == PINS["cost"][name]


# -- the weight layout ---------------------------------------------------------
DECODER_LAYOUT = decoder.weight_layout


def two_stacks(m):
    """A dense first layer beside a stack of MoE layers, as a published
    DeepSeek-MoE has them."""
    moe = dict(m, num_hidden_layers=m["num_hidden_layers"] - 1)
    dense = dict(m, family="dense", intermediate_size=4 * m["hidden_size"])
    tops, [(_, _, moe_specs)] = DECODER_LAYOUT(moe)
    _, [(_, _, dense_specs)] = DECODER_LAYOUT(dense)
    return tops, [("dense_layers", 1, dense_specs),
                  ("layers", moe["num_hidden_layers"], moe_specs)]


def test_a_layout_holds_a_dense_layer_beside_an_moe_stack(monkeypatch):
    m = dict(spec.load_json(FIX / "tiny-moe.json"), num_hidden_layers=3)
    monkeypatch.setattr(decoder, "weight_layout", two_stacks)
    p = weights.make_params(m, 5)
    assert p["dense_layers"]["mlp"]["w_up"].shape == (1, 256, 1024)
    assert p["layers"]["moe"]["w_up"].shape == (2, 16, 256, 128)
    one = weights.layer(m, 5, 0, stack="dense_layers")
    assert np.array_equal(np.asarray(one["mlp/w_down"]),
                          np.asarray(p["dense_layers"]["mlp"]["w_down"][0]))
    assert np.array_equal(np.asarray(one["attn/wq"]),
                          np.asarray(p["dense_layers"]["attn"]["wq"][0]))
    last = weights.layer(m, 5, 1, stack="layers")
    assert np.array_equal(np.asarray(last["moe/router"]),
                          np.asarray(p["layers"]["moe"]["router"][1]))
    # the stacks draw apart: the same leaf path differs between them
    assert not np.array_equal(np.asarray(one["attn/wq"]),
                              np.asarray(p["layers"]["attn"]["wq"][0]))
    with pytest.raises(ValueError, match="name one"):
        weights.layer(m, 5, 0)


# -- the resolver -------------------------------------------------------------
def test_every_config_names_a_sound_module():
    for path in CONFIG_FILES.values():
        m = spec.load_json(path)
        assert spec.reference_problems(m) == []
        assert spec.reference(m) is decoder


def partial_module(name, lack):
    mod = types.ModuleType(f"bench.references.{name}")
    for f in spec.REFERENCE_FUNCTIONS:
        if f != lack:
            setattr(mod, f, getattr(decoder, f))
    return mod


@pytest.mark.parametrize("lack", spec.REFERENCE_FUNCTIONS)
def test_problems_flag_a_module_without_a_function(monkeypatch, lack):
    monkeypatch.setitem(sys.modules, "bench.references.half_arch",
                        partial_module("half_arch", lack))
    m = dict(spec.load_json(FIX / "tiny-dense.json"), reference="half_arch")
    assert spec.reference_problems(m) == [
        f"config tiny-dense: references/half_arch.py lacks ['{lack}']"]


@pytest.mark.parametrize("reference,found", [
    (None, "config tiny-dense: no reference"),
    ("no_such_arch", "config tiny-dense: no module "
                     "references/no_such_arch.py"),
    ("../decoder", "config tiny-dense: reference '../decoder' names no "
                   "module of bench/references"),
])
def test_problems_flag_a_missing_module(reference, found):
    m = spec.load_json(FIX / "tiny-dense.json")
    if reference is None:
        del m["reference"]
    else:
        m["reference"] = reference
    assert spec.reference_problems(m) == [found]


def test_problems_read_each_config_files_module(tmp_path):
    """``spec.problems`` resolves the module of every configuration file
    of the benchmark it checks."""
    bench, _ = probe_benchmark(tmp_path, "no_such_arch")
    assert spec.problems(bench, tmp_path) == [
        "config probe-tiny: no module references/no_such_arch.py"]


# -- new files only ------------------------------------------------------------
def probe_module(calls):
    """A new architecture that exists only in ``sys.modules``: the
    decoder's mathematics, every function counting its calls."""
    mod = types.ModuleType("bench.references.probe_arch")
    for f in spec.REFERENCE_FUNCTIONS:
        def counted(*a, _f=getattr(decoder, f), _name=f, **k):
            calls[_name] = calls.get(_name, 0) + 1
            return _f(*a, **k)
        setattr(mod, f, counted)
    return mod


def probe_benchmark(root, reference):
    """A benchmark whose one configuration file, under ``root``, names
    ``reference``; the cell runs an existing mix and metric."""
    m = dict(spec.load_json(FIX / "tiny-dense.json"), name="probe-tiny",
             reference=reference)
    (root / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    (root / "bench" / "configs" / "probe-tiny.json").write_text(
        json.dumps(m))
    real = spec.load_benchmark()
    cell = "probe-tiny.decode_long"
    step = dict(spec.find_metric(real, "decode_step_ms"), workloads=[cell])
    bench = dict(copy.deepcopy(real), configs=[{
        "name": "probe-tiny", "source": "test", "why": "test", "reduced": [],
        "file": "bench/configs/probe-tiny.json"}],
        workloads=[{"name": cell, "config": "probe-tiny", "chips": 1,
                    "traffic": "decode_long", "why": "test"}],
        end_to_end=[{k: v for k, v in e.items() if k != "workloads"}
                    for e in real["end_to_end"]],
        per_layer=[step])
    return bench, m


def test_a_new_architecture_is_new_files_only(monkeypatch, tmp_path):
    calls = {}
    monkeypatch.setitem(sys.modules, "bench.references.probe_arch",
                        probe_module(calls))
    bench, m = probe_benchmark(tmp_path, "probe_arch")
    assert spec.problems(bench, tmp_path) == []
    assert not (spec.BENCH_DIR / "references" / "probe_arch.py").exists()
    cell = {"workload": {"name": "probe-tiny.tiny-chat", "chips": 1},
            "config": m, "traffic": spec.load_json(FIX / "tiny-chat.json"),
            "load": {"rate_per_s": 6.0}, "end_to_end": bench["end_to_end"],
            "per_layer": []}
    cell["generator"] = spec.traffic_generator(cell["traffic"]["kind"])
    res, cmp = run.run_cell(cell, 7, 2.0, False, jax.devices(),
                            log=lambda s: None)
    assert res["correct"], res["compared"]
    assert cmp["tokens"] > 0
    assert "by_margin" not in cmp          # the probe is dense: no routing
    assert cost.drain_bytes(m, 64, ROWS) == PINS["cost"]["tiny-dense"][
        "drain_bytes"]
    assert set(calls) == set(spec.REFERENCE_FUNCTIONS)


@pytest.mark.parametrize("margin", [np.inf, 0.015])
def test_by_margin_follows_the_margins_not_the_family(monkeypatch, margin):
    """``compare`` reads the gaps by routing margin wherever the
    reference reports a finite margin, whatever the configuration."""
    mod = partial_module("margin_arch", None)

    def logit_gaps(m, seed, prompts, served, control=False):
        return [{"gap": np.zeros(len(s), np.float32),
                 "margin": np.full(len(s), margin, np.float32)}
                for s in served]

    mod.logit_gaps = logit_gaps
    monkeypatch.setitem(sys.modules, "bench.references.margin_arch", mod)
    m = dict(spec.load_json(FIX / "tiny-dense.json"), reference="margin_arch")
    drv = types.SimpleNamespace(req={
        i: {"prompt": np.arange(2, 9), "served": [3] * (i + 1),
            "max_new": i + 1, "preemptions": 0} for i in range(2)})
    cmp = run.compare(m, 11, drv, [0, 1])
    if np.isinf(margin):
        assert "by_margin" not in cmp
    else:
        assert [row[0] for row in cmp["by_margin"]] == list(run.MARGINS)
        assert [row[3] for row in cmp["by_margin"]] == [
            float(margin < mg) for mg in run.MARGINS]


# -- lint ----------------------------------------------------------------------
def arch_key_reads(source):
    """``(line, key)`` of every ``x["key"]`` and ``x.get("key")`` of an
    architecture's key in ``source``."""
    out = []
    for node in ast.walk(ast.parse(source)):
        key = None
        if isinstance(node, ast.Subscript) \
                and isinstance(node.slice, ast.Constant):
            key = node.slice.value
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "get" and node.args \
                and isinstance(node.args[0], ast.Constant):
            key = node.args[0].value
        if key in ARCH_KEYS:
            out.append((node.lineno, key))
    return out


def test_the_lint_finds_a_planted_read():
    assert arch_key_reads('if m["family"] == "moe":\n    e = '
                          'm.get("n_routed_experts", 0)\n') == [
        (1, "family"), (2, "n_routed_experts")]


def test_no_harness_file_reads_an_architectures_keys():
    found = {}
    for path in sorted(spec.BENCH_DIR.rglob("*.py")):
        rel = path.relative_to(spec.BENCH_DIR)
        if rel.parts[0] == "references":
            continue
        reads = arch_key_reads(path.read_text())
        if reads:
            found[str(rel)] = reads
    assert found == {}
