"""The comparison that decides ``correct``, driven through a whole run of
the harness at a size the CPU holds (the look for a chip skipped): a
sound run passes, and each fault a serving cell can have, planted in the
timed path, fails it; the int8 control reads above the limit.

The tiny configurations keep yi-6b's block (GQA, SwiGLU, RMSNorm, rotary)
and deepseek-moe-16b's (top-k routing, shared experts) at small widths,
in bf16 like the chip cells; their limits (and the MoE routing margin)
come from these readings as the chip cells' come from theirs (PERF.md).
"""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import run, spec, weights  # noqa: E402

FIX = spec.BENCH_DIR / "tests" / "fixtures"
SECONDS = 2.0
#: yi-6b's block and deepseek-moe-16b's (routing margin and all)
CONFIGS = ["tiny-dense", "tiny-moe"]


def tiny_cell(config="tiny-dense"):
    cell = {"workload": {"name": f"{config}.tiny-chat", "chips": 1},
            "config": spec.load_json(FIX / f"{config}.json"),
            "traffic": spec.load_json(FIX / "tiny-chat.json"),
            "load": {"rate_per_s": 6.0},
            "end_to_end": spec.load_benchmark()["end_to_end"],
            "per_layer": []}
    cell["generator"] = spec.traffic_generator(cell["traffic"]["kind"])
    return cell


def serve(fault=None, seed=7, control=False, config="tiny-dense"):
    return run.run_cell(tiny_cell(config), seed, SECONDS, False,
                        jax.devices(), log=lambda s: None, fault=fault,
                        control=control)[0]


def test_weights_layer_regenerates_the_stacked_leaves():
    m = tiny_cell()["config"]
    full = weights.make_params(m, 2**31 + 5)
    for lay in (0, m["num_hidden_layers"] - 1):
        one = weights.layer(m, 2**31 + 5, lay)
        assert np.array_equal(np.asarray(one["mlp/w_up"]),
                              np.asarray(full["layers"]["mlp"]["w_up"][lay]))
        assert np.array_equal(np.asarray(one["attn/wk"]),
                              np.asarray(full["layers"]["attn"]["wk"][lay]))
    top = weights.top(m, 2**31 + 5)
    assert np.array_equal(np.asarray(top["lm_head"]),
                          np.asarray(full["lm_head"]))


@pytest.mark.parametrize("config", CONFIGS)
def test_sound_run_is_correct(config):
    res = serve(config=config)
    cmp = res["compared"]["max_logit_gap"]
    assert res["correct"], res["compared"]
    assert cmp["value"] <= cmp["limit"]
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"]
                                   for m in tiny_cell()["end_to_end"]}
    assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("config", CONFIGS)
def test_fault_state_unchanged_fails(monkeypatch, config):
    """The decode step returns its KV pools unchanged: every served
    token's key and value is lost."""
    from repro.launch.serve import ServingEngine
    orig = ServingEngine._decode_fn

    def broken(self, params, k_pools, v_pools, *a):
        logits, _, _ = orig(self, params, k_pools, v_pools, *a)
        return logits, k_pools, v_pools

    monkeypatch.setattr(ServingEngine, "_decode_fn", broken)
    res = serve(config=config)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("config", CONFIGS)
def test_fault_token_altered_fails(config):
    """Every seventh token the engine picks is replaced where it is
    produced (the next id), and served and fed back as such."""
    def fault(eng, drv):
        calls = {"n": 0}

        def pick(lg):
            calls["n"] += 1
            t = int(np.argmax(lg))
            return (t + 1) % lg.shape[-1] if calls["n"] % 7 == 0 else t

        drv.sample_fn = pick

    res = serve(fault=fault, config=config)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("config", CONFIGS[:1])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_reads_above_the_limit(seed, config):
    """The reference in int8 (W8A8) in the program's place, through a
    whole run: its first choices, read at every position of the prompts
    and tokens the run served, lie further below the float32 reference's
    best than the limit allows, so ``correct`` is false."""
    res = serve(seed=seed, control=True, config=config)
    cmp = res["compared"]["max_logit_gap"]
    assert cmp["value"] > cmp["limit"]
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("name", ["tiny-dense", "tiny-moe"])
def test_reference_agrees_with_the_program_in_float32(name):
    """The plain reference against the program's own prefill run in
    float32 on the same weights: the program's first choice lies within
    the program's bf16 LM-head rounding of the reference's best.  The MoE
    case covers routing, renormalised gates, the prompt's capacity drops
    and the shared experts."""
    from repro.models import build_model
    m = spec.load_json(FIX / f"{name}.json")
    m32 = dict(m, torch_dtype="float32",
               overrides=dict(m["overrides"], dtype="float32"))
    model = build_model(run.program_config(m32))
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                    weights.make_params(m, 11))
    prefill = jax.jit(lambda p, t: model.prefill(
        p, {"tokens": t}, None, margin_tokens=0)[0])
    rng = np.random.default_rng(1)
    prompts, served = [], []
    for n in (70, 190):
        p = rng.integers(2, m["vocab_size"], size=n).astype(np.int32)
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(prefill(params, jnp.asarray(p[None])))[0]
        prompts.append(p)
        served.append([int(np.argmax(lg))])
    gaps = spec.reference(m).logit_gaps(m, 11, prompts, served)
    assert max(float(g["gap"].max()) for g in gaps) < 0.02
