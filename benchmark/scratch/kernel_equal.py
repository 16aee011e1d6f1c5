#!/usr/bin/env python3
"""Two trees' ``flash_attention`` on the same bf16 inputs, on the chip: o, dq,
dk, dv of each against the other (largest difference, share of equal
elements) and against ``mha_reference`` in float32. How PR 29 showed that
the merged-layout kernels compute what the parent's did.

    python3 benchmark/scratch/kernel_equal.py <parent tree> <change tree> [tiny]

One JSON object on stdout. ``tiny`` is the CPU rehearsal's size.
"""
import importlib
import json
import sys

SHAPES = [(8, 1024, 16, 64, 1024), (2, 1024, 8, 128, 1024),
          (2, 1024, 4, 64, 512)]    # (B, S, H, hd, block)
TINY = [(1, 256, 2, 64, 256), (1, 256, 2, 64, 128)]


def load(root):
    """``ray_tpu.ops.flash_attention`` and ``mha_reference`` of the tree
    at ``root``: the package is imported anew from there."""
    sys.path.insert(0, root)
    for m in [m for m in sys.modules
              if m == "ray_tpu" or m.startswith("ray_tpu.")]:
        del sys.modules[m]
    mod = importlib.import_module("ray_tpu.ops.flash_attention")
    ref = importlib.import_module("ray_tpu.ops.attention").mha_reference
    sys.path.pop(0)
    assert mod.__file__.startswith(root), mod.__file__
    return mod, ref


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    par, _ = load(sys.argv[1])
    chg, ref = load(sys.argv[2])
    f32 = jnp.float32
    out = {"device": jax.devices()[0].device_kind}
    for b, s, h, d, blk in (TINY if len(sys.argv) > 3 else SHAPES):
        keys = jax.random.split(jax.random.PRNGKey(b * s + h), 4)
        q, k, v, w = [jax.random.normal(x, (b, s, h, d), f32).astype(
            jnp.bfloat16) for x in keys]

        def run(mod):
            def attn(q, k, v):
                return mod.flash_attention(q, k, v, causal=True,
                                           block_q=blk, block_k=blk)

            def loss(q, k, v):
                return (attn(q, k, v).astype(f32) * w.astype(f32)).sum()

            got = (jax.jit(attn)(q, k, v),
                   *jax.jit(jax.grad(loss, (0, 1, 2)))(q, k, v))
            return [np.asarray(x, np.float32) for x in got]

        def ref_loss(q, k, v):
            return (ref(q, k, v, causal=True) * w.astype(f32)).sum()

        rp, rc = run(par), run(chg)
        q32, k32, v32 = (x.astype(f32) for x in (q, k, v))
        rr = [np.asarray(x) for x in (
            ref(q32, k32, v32, causal=True),
            *jax.grad(ref_loss, (0, 1, 2))(q32, k32, v32))]
        row = {}
        for name, a, c, r in zip(("o", "dq", "dk", "dv"), rp, rc, rr):
            row[name] = {
                "max_abs_change_vs_parent": float(np.abs(a - c).max()),
                "share_of_elements_equal": float((a == c).mean()),
                "max_abs_parent_vs_f32": float(np.abs(a - r).max()),
                "max_abs_change_vs_f32": float(np.abs(c - r).max()),
                "rms_parent_vs_f32": float(np.sqrt(((a - r) ** 2).mean())),
                "rms_change_vs_f32": float(np.sqrt(((c - r) ** 2).mean())),
            }
        out[f"B{b}_S{s}_H{h}_hd{d}_blk{blk}"] = row
    out["path_counts_change"] = dict(getattr(chg, "PATH_COUNTS", {}))
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
