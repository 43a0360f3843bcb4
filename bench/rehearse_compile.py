"""Compile each configuration's programs for a described TPU v5e, without
the chip, and print what the compiler says they need.

    JAX_PLATFORMS=cpu python3 bench/rehearse_compile.py [config ...]

For every configuration file (default: all under ``bench/configs``) it
compiles, at the served sizes: the weight init of ``bench/weights.py``
over the configuration's weight layout (its module in
``bench/references/``, resolved as ``bench/run.py`` resolves it), the
engine's decode step over the full pools, and its prefill of the
longest prompt the benchmark's mixes send.  It prints each program's
``memory_analysis()`` and the bytes the engine keeps resident (weights and
pools), so a configuration that cannot fit one chip is found before a chip
run.  Arguments are shapes only: nothing is placed on a device.
"""
from __future__ import annotations

import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import spec, weights  # noqa: E402
from bench.run import program_config  # noqa: E402

GB = 1e9


def longest_prompt() -> int:
    """The longest prompt any mix in ``bench/traffic`` sends."""
    best = 0
    for path in (spec.BENCH_DIR / "traffic").glob("*.json"):
        mix = spec.load_json(path)
        gen = spec.traffic_generator(mix["kind"])
        best = max(best, max(gen.make(mix, 0, {"vocab_size": 1000},
                                      {"rate_per_s": 1.0}).prompt_lengths))
    return best


def describe(tag: str, compiled) -> int:
    ma = compiled.memory_analysis()
    args, out, tmp = (ma.argument_size_in_bytes, ma.output_size_in_bytes,
                      ma.temp_size_in_bytes)
    alias = getattr(ma, "alias_size_in_bytes", 0)
    print(f"  {tag}: arguments {args / GB:.3f} GB, outputs {out / GB:.3f} "
          f"GB, temporaries {tmp / GB:.3f} GB, aliased {alias / GB:.3f} GB")
    return tmp


def rehearse(m: dict, one) -> None:
    from repro.launch.serve import ServingEngine
    cfg = program_config(m)
    e = m["engine"]
    print(f"{m['name']}:")

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    key = sds(jax.eval_shape(lambda: weights.root_key(0)))
    init = jax.jit(weights.init_fn(m)).lower(key).compile()
    describe("weight init", init)
    params = jax.eval_shape(weights.init_fn(m), key)
    p_bytes = sum(np.prod(x.shape) * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    params = jax.tree_util.tree_map(sds, params)
    with jax.default_device(jax.devices("cpu")[0]):
        eng = ServingEngine(cfg, None, max_seqs=e["max_seqs"],
                            max_blocks_per_seq=e["max_blocks_per_seq"],
                            max_admit_pages=e["max_admit_pages"],
                            spill_pages=e["spill_pages"],
                            dedup_admit=e["dedup_admit"],
                            adaptive_ring=e["adaptive_ring"])
    pools = {n: sds(a) for n, a in eng.engine.pools.items()}
    pool_bytes = eng.engine.pool_bytes_resident()
    table, mask, base = eng.cache.device_tables()
    B = e["max_seqs"]
    i32 = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one)
    dec = jax.jit(eng._decode_fn, donate_argnums=(1, 2)).lower(
        params, pools["k"], pools["v"], sds(table), sds(mask), sds(base),
        i32, i32, None).compile()
    t_dec = describe("decode step", dec)
    S = longest_prompt()
    page = eng.cache.page
    batch = {"tokens": jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=one)}
    ids = jax.ShapeDtypeStruct((-(-S // page),), jnp.int32, sharding=one)
    pre = jax.jit(eng._prefill_stage_fn, donate_argnums=(2, 3)).lower(
        params, batch, pools["k_stage"], pools["v_stage"], ids).compile()
    t_pre = describe(f"prefill of {S} tokens", pre)
    resident = p_bytes + pool_bytes
    print(f"  resident: weights {p_bytes / GB:.3f} GB + pools "
          f"{pool_bytes / GB:.3f} GB = {resident / GB:.3f} GB; with the "
          f"decode step's temporaries {(resident + t_dec) / GB:.3f} GB, "
          f"the prefill's {(resident + t_pre) / GB:.3f} GB")


def main(argv) -> int:
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    names = argv or sorted(p.stem for p in
                           (spec.BENCH_DIR / "configs").glob("*.json"))
    for name in names:
        rehearse(spec.load_json(spec.BENCH_DIR / "configs" / f"{name}.json"),
                 one)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
