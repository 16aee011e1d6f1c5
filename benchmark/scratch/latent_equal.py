#!/usr/bin/env python3
"""Two trees' LATENT ``flash_attention`` (a score in two parts, one shared
rope key) on the same bf16 inputs, on the chip, at the cell's shape: o and
the five gradients of each tree against the other's (largest difference,
share of equal elements) and against a float32 reference worked one head
at a time (the [S, S] scores of all heads do not fit the chip at S=8192).
``kernel_equal.py``'s measure, for the kernels it does not reach. PR 34.

    python3 benchmark/scratch/latent_equal.py <parent tree> <change tree> [tiny]

One JSON object on stdout. ``tiny`` is the CPU rehearsal's size.
"""
import json
import sys

from kernel_equal import load

SHAPE = (2, 8192, 32, 1024)     # (B, S, H, block): kanana2_train_s8192
TINY = (1, 512, 4, 128)
DN, DR, DV = 128, 64, 128
NAMES = ("o", "dq_nope", "dk_nope", "dv", "dq_rope", "dk_rope")


def reference(q, k, v, qr, kr, w):
    """o and the five gradients of sum(o * w) in float32, a head at a
    time: plain softmax attention over the 192-wide key written out."""
    import jax
    import jax.numpy as jnp

    s_len = q.shape[1]
    scale = (DN + DR) ** -0.5
    hi = jax.lax.Precision.HIGHEST
    mask = jnp.tril(jnp.ones((s_len, s_len), bool))

    def head(qh, kh, vh, qrh, krb):             # [S, d] each
        s = (jnp.dot(qh, kh.T, precision=hi)
             + jnp.dot(qrh, krb.T, precision=hi)) * scale
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return jnp.dot(p, vh, precision=hi)

    def one(args):
        qh, kh, vh, qrh, krb, wh = args
        o, vjp = jax.vjp(head, qh, kh, vh, qrh, krb)
        return (o, *vjp(wh))

    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    by_head = lambda x: f32(x).transpose(0, 2, 1, 3).reshape(  # noqa: E731
        -1, s_len, x.shape[-1])
    h = q.shape[2]
    got = jax.lax.map(one, (by_head(q), by_head(k), by_head(v), by_head(qr),
                            jnp.repeat(f32(kr), h, axis=0), by_head(w)))
    back = lambda x: x.reshape(q.shape[0], h, s_len, -1).transpose(  # noqa: E731
        0, 2, 1, 3)
    o, dq, dk, dv, dqr, dkr = (back(x) for x in got)
    return o, dq, dk, dv, dqr, dkr.sum(axis=2)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    par, _ = load(sys.argv[1])
    chg, _ = load(sys.argv[2])
    b, s, h, blk = TINY if len(sys.argv) > 3 else SHAPE
    f32 = jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(34), 6)
    rnd = lambda key, *sh: jax.random.normal(key, sh, f32).astype(  # noqa: E731
        jnp.bfloat16)
    q, k = rnd(keys[0], b, s, h, DN), rnd(keys[1], b, s, h, DN)
    v, w = rnd(keys[2], b, s, h, DV), rnd(keys[5], b, s, h, DV)
    qr, kr = rnd(keys[3], b, s, h, DR), rnd(keys[4], b, s, DR)

    def run(mod):
        def attn(q, k, v, qr, kr):
            return mod.flash_attention(q, k, v, causal=True, block_q=blk,
                                       block_k=blk, q_rope=qr, k_rope=kr)

        def loss(*a):
            return (attn(*a).astype(f32) * w.astype(f32)).sum()

        got = (jax.jit(attn)(q, k, v, qr, kr),
               *jax.jit(jax.grad(loss, (0, 1, 2, 3, 4)))(q, k, v, qr, kr))
        return [np.asarray(x, np.float32) for x in got]

    rp, rc = run(par), run(chg)
    rr = [np.asarray(x) for x in jax.jit(reference)(q, k, v, qr, kr, w)]
    out = {"device": jax.devices()[0].device_kind,
           "shape": {"B": b, "S": s, "H": h, "block": blk},
           "backward_counts_change": dict(getattr(chg, "BACKWARD_COUNTS", {}))}
    for name, a, c, r in zip(NAMES, rp, rc, rr):
        out[name] = {
            "max_abs_change_vs_parent": float(np.abs(a - c).max()),
            "share_of_elements_equal": float((a == c).mean()),
            "max_abs_parent_vs_f32": float(np.abs(a - r).max()),
            "max_abs_change_vs_f32": float(np.abs(c - r).max()),
            "rms_parent_vs_f32": float(np.sqrt(((a - r) ** 2).mean())),
            "rms_change_vs_f32": float(np.sqrt(((c - r) ** 2).mean())),
            "rms_f32": float(np.sqrt((r ** 2).mean())),
        }
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
