"""Compile for a TPU v5e WITHOUT one.

jax's ahead-of-time topology (`jax.experimental.topologies`) plus the
installed libtpu compile a jitted function — Mosaic kernels included —
for a described v5e from a CPU-only sandbox. Nothing runs: this answers
"does it lower, does Mosaic accept it, does it fit the chip's memory",
which is most of what a first chip run used to be spent finding out.
It says nothing about speed or numerics; those need the chip.

    python tools/tpu_aot.py            # the Pallas kernels at the shipped
                                       # shapes and every tuned-table row

libtpu takes a machine-wide lock: one process at a time can use it, so
a second concurrent caller fails at `topology()` — callers that can do
without (the tier-1 test) treat that as a skip.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_TOPOLOGY = None


def topology(name="v5e:2x2"):
    """The described (not attached) TPU topology: four v5e devices."""
    global _TOPOLOGY
    if _TOPOLOGY is None:
        from jax.experimental import topologies
        _TOPOLOGY = topologies.get_topology_desc(name, "tpu")
    return _TOPOLOGY


def compile_for_v5e(fn, *avals):
    """Lower `fn` for TPU and compile it for ONE described v5e device.
    `avals` are arrays or ShapeDtypeStructs (only shape/dtype are used).
    Returns the jax.stages.Compiled (`.as_text()`, `.memory_analysis()`);
    raises what the compiler raises — a Mosaic refusal, or
    RESOURCE_EXHAUSTED when the program does not fit 16 GB of HBM."""
    import jax
    from jax.sharding import SingleDeviceSharding
    sharding = SingleDeviceSharding(topology().devices[0])
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        avals)
    return jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).compile()


def kernel_cases():
    """[(name, fn, avals)]: every Pallas kernel at the train shape, the
    serve shapes, and every row of ops/pallas/flash_blocks_tuned.json —
    what tests/test_tpu_lowering.py lowers in-process and compiles here."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate import autotune
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_decode_attention)

    S = jax.ShapeDtypeStruct
    table = autotune._read_cache_file(autotune._SHIPPED_PATH)
    cases = []

    def flash_case(tag, B, H, L, D, causal, blocks):
        q = S((B, H, L, D), jnp.bfloat16)
        bq, bk = blocks

        def fwd_bwd(q, k, v):
            def loss(q, k, v):
                return flash_attention(
                    q, k, v, causal=causal, block_q=bq, block_k=bk,
                    interpret=False).astype(jnp.float32).sum()
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        cases.append((f"flash[{tag}] B{B} H{H} S{L} D{D} causal={causal} "
                      f"blocks={blocks}", fwd_bwd, (q, q, q)))

    # the train shape with the kernel's own default blocks, then each row
    flash_case("default", 8, 16, 1024, 64, True, (None, None))
    for key, blocks in sorted(table.items(), key=repr):
        if key[0] == "paged":
            continue
        _, H, L, D, causal = key
        flash_case("row", 8, H, L, D, causal, tuple(blocks))

    def paged_case(tag, H, L, D, bs, caps, slots=8):
        nb = L // bs
        N = slots * nb + 1
        tables, pos = S((slots, nb), jnp.int32), S((slots,), jnp.int32)
        for T in (1, 128):          # decode; a prefill bucket (larger
            # buckets tile the same way, only the grid grows)
            for quant in (False, True):
                q = S((slots, T, H, D), jnp.float32)
                pool = S((N, bs, H, D), jnp.int8 if quant else jnp.float32)
                scales = (S((N, H), jnp.float32),) * 2 if quant else ()

                def fn(q, k, v, tables, pos, *sc, caps=caps):
                    return paged_attention(
                        q, k, v, tables, pos, q_tile=caps[0],
                        head_tile=caps[1], interpret=False,
                        k_scale=sc[0] if sc else None,
                        v_scale=sc[1] if sc else None)
                cases.append((
                    f"paged[{tag}] H{H} L{L} D{D} bs{bs} T{T} "
                    f"{'int8' if quant else 'float'} caps={caps}",
                    fn, (q, pool, pool, tables, pos) + scales))

    # the smoke's serve geometry (gpt_1p3b heads) at the default caps, the
    # same heads sharded four ways (tp=4), then each shipped row
    paged_case("default", 16, 1024, 128, 16, (None, None))
    paged_case("tp4", 4, 1024, 128, 16, (None, None))
    for key, caps in sorted(table.items(), key=repr):
        if key[0] != "paged":
            continue
        _, _, H, L, D, bs = key
        paged_case("row", H, L, D, bs, tuple(caps))

    def decode_case(H, L, D, bs, dtype, slots=8):
        nb = L // bs
        pool = S((slots * nb + 1, bs, H, D), dtype)

        def fn(q, k, v, tables, pos):
            return paged_decode_attention(q, k, v, tables, pos,
                                          interpret=False)
        cases.append((
            f"paged_decode H{H} L{L} D{D} bs{bs} {jnp.dtype(dtype).name}",
            fn, (S((slots, 1, H, D), jnp.float32), pool, pool,
                 S((slots, nb), jnp.int32), S((slots,), jnp.int32))))

    # the decode kernel at the serve cell's shape, over bfloat16 pools, at
    # block size 8, and at twice the heads and head size
    decode_case(16, 1024, 128, 16, jnp.float32)
    decode_case(16, 1024, 128, 16, jnp.bfloat16)
    decode_case(16, 1024, 128, 8, jnp.float32)
    decode_case(32, 2048, 256, 16, jnp.bfloat16, slots=4)
    return cases


def main():
    try:
        topology()
    except Exception as e:                                   # noqa: BLE001
        # no libtpu, or another process holds its lock: nothing was judged
        print(json.dumps({"skipped": f"{type(e).__name__}: {str(e)[:300]}"}))
        return 3
    failed = []
    cases = kernel_cases()
    for name, fn, avals in cases:
        try:
            compile_for_v5e(fn, *avals)
            print(f"ok   {name}", flush=True)
        except Exception as e:                               # noqa: BLE001
            failed.append(name)
            print(f"FAIL {name}: {type(e).__name__}: "
                  f"{str(e)[:400]}".replace("\n", " | "), flush=True)
    print(json.dumps({"compiled_for": "TPU v5e (described, not attached)",
                      "cases": len(cases), "failed": failed}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
