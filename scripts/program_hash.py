"""The serving programs of a configuration as they are LOWERED for a
v5e, hashed: what "this change leaves that model's programs alone" means,
shown without a chip.

    JAX_PLATFORMS=cpu python scripts/program_hash.py [--tree DIR] \
        joyai-llm-flash trinity-mini dots3-note-prev

For each configuration (``benchmark/configs/<name>.json``, its ``fields``)
the ragged step and the decode window are traced over shapes alone
(``jax.eval_shape`` parameters and cache, a described ``v5e:2x2`` device:
nothing runs, nothing is allocated) and the StableHLO text is hashed with
each Pallas kernel's serialized body printed as MLIR WITHOUT its debug
locations: a kernel's bytes hold the file and line of every call site on
its way, so an edit a hundred lines above an untouched function moves
them and nothing else. Two trees agree on a line exactly when the
program is the same letter for letter. ``--tree`` hashes another
checkout (the parent's, unpacked under ``.scratch/``); run the two in
separate processes. The shapes are the benchmark cells' buckets; they
only have to be the same on both sides.
"""

import argparse
import base64
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

# a cell's launch: token bucket, table rows and pages, pool blocks, and a
# row's ring of window blocks where the model has one
CASES = {
    "joyai-llm-flash": dict(T=8192, R=64, MB=24, nb=1600, ring=0),
    "trinity-mini": dict(T=1024, R=16, MB=64, nb=1100, ring=10),
    "dots3-note-prev": dict(T=4096, R=4, MB=2064, nb=16521, ring=98),
}


def without_locations(text: str) -> bytes:
    """``text`` with every ``tpu_custom_call`` body (base64 of MLIR
    bytecode) printed as MLIR without debug info."""
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib.mlir import ir
    ctx = jmlir.make_ir_context()
    ctx.allow_unregistered_dialects = True

    def body(m):
        with ctx:
            module = ir.Module.parse(base64.b64decode(m.group(2)))
            return m.group(1) + module.operation.get_asm(
                enable_debug_info=False)
    return re.sub(r'(body\\22: \\22)([A-Za-z0-9+/=]+)', body, text).encode()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("configs", nargs="*", default=list(CASES))
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    args = ap.parse_args()
    sys.path.insert(0, args.tree)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    # the programs' own tests of the backend take the chip's branch
    jax.default_backend = lambda: "tpu"
    from deepspeed_tpu.inference.v2.paged_model import (
        init_paged_kv_cache, paged_decode_window, paged_ragged_step)
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        "v5e:2x2", platform="tpu").devices[0])

    def on(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    for name in args.configs:
        c = CASES[name]
        with open(os.path.join(args.tree, "benchmark", "configs",
                               f"{name}.json")) as f:
            cfg = TransformerConfig(**json.load(f)["fields"])
        params = jax.tree.map(
            lambda x: on(x, jnp.bfloat16),
            jax.eval_shape(TransformerLM(cfg).init_params,
                           jax.random.PRNGKey(0)))
        T, R, MB, ring = c["T"], c["R"], c["MB"], c["ring"]
        cache = jax.tree.map(on, jax.eval_shape(
            lambda: init_paged_kv_cache(
                cfg, c["nb"], 16, jnp.bfloat16,
                **(dict(window_blocks=R * ring + 1) if ring else {}))))
        rings = (i32(R, ring),) if ring else ()

        def window(wt):
            return dict(window_tables=wt[0]) if wt else {}
        programs = {
            "ragged_step": jax.jit(
                lambda p, ids, rows, pos, ln, wb, wo, bt, li, c, *wt:
                paged_ragged_step(cfg, p, ids, rows, pos, ln, wb, wo, bt, li,
                                  c, 16, use_kernel=True, **window(wt)),
                donate_argnums=(9,)).lower(
                    params, *(i32(T),) * 6, i32(R, MB), i32(R), cache,
                    *rings),
            "decode_window": jax.jit(
                lambda p, t, pos, bt, c, sl, eos, alive, *wt:
                paged_decode_window(cfg, p, t, pos, bt, c, sl, eos, 16, 8,
                                    use_kernel=True, alive=alive,
                                    **window(wt)),
                donate_argnums=(4,)).lower(
                    params, i32(R), i32(R), i32(R, MB), cache, i32(R),
                    i32(R), jax.ShapeDtypeStruct((R,), jnp.bool_,
                                                 sharding=chip), *rings)}
        for program, lowered in programs.items():
            print(name, program, hashlib.sha256(without_locations(
                lowered.as_text())).hexdigest()[:16], flush=True)


if __name__ == "__main__":
    main()
