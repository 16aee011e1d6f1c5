#!/usr/bin/env python3
"""ONE expert layer at ``nemotron3super_train_s8192``'s shape on the chip, its
pair domain compacted (``held_expert_layer`` as it is: a token's held choices
in ``experts_held`` slots) against the [T, k] pair domain every layer had
before PR 57, composed by hand from the layer's own parts (PR 57):

    chiprun -- python3 scripts/pair_domain_chip.py [--bias 0,0.5]
    python3 scripts/pair_domain_chip.py --tiny          # walks it here

16 384 tokens of 4096 in bfloat16, 8 of 512 squared-ReLU experts of
1024 x 2688 in a latent of 1024, a shared expert of 5376, sigmoid top 22,
weights drawn from ``--seed``; ``--bias b`` adds b to the held experts'
selection bias, so that more tokens hold several rows. For each bias it says
whether the two row buffers are EQUAL, in how many elements and by how much the
two outputs differ (the sum over a token's rows adds the same terms in another
tree; a token that holds under three rows cannot differ), by the rows a token
holds, and what forward + backward of the layer takes either way, with the
difference a pair taken out of the pair domain (ns a row: ROADMAP A15(1)(a)
asks that a row gather be timed against this). A script, not a metric."""
import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from ray_tpu.ops import expert_layer as el  # noqa: E402

CELL = dict(t=16384, d=4096, latent=1024, e=512, held=8, f=2688, fs=5376,
            top_k=22, scale=5.0, tile=el.ROW_TILE)
TINY = dict(t=256, d=64, latent=32, e=32, held=8, f=48, fs=64, top_k=22,
            scale=5.0, tile=8)


def by_hand(x, p, s, compact):
    """The layer from its parts, on [T, k] or, ``compact``, on [T, held] ->
    (output, the row buffer, rows a token holds)."""
    dt = x.dtype
    rows = el.buffer_rows(s["t"], s["top_k"], s["held"], s["tile"])
    weights, chosen = el.route(x, p["w_router"], p["router_bias"],
                               top_k=s["top_k"], routed_scale=s["scale"])
    if compact:
        weights, chosen = el.compact_held(weights, chosen, s["held"], 0)
    at = el.sort_rows(chosen, s["held"], 0, rows, s["tile"])
    buf = el.tokens_to_rows(jnp.dot(x, p["w_fc1"].astype(dt)), at)
    y = el._mlp("relu2", buf, p, "e",
                lambda a, w: el.grouped_matmul(a, w, at["tile_expert"],
                                               at["n_used"], s["tile"]),
                el.pairs_to_rows(weights, at))
    routed = jnp.dot(el.rows_to_tokens(y, at), p["w_fc2"].astype(dt))
    return (el._mlp("relu2", x, p, "s", jnp.dot) + routed, buf,
            jnp.sum(at["pair_held"], axis=1))


def the_layer(x, p, s):
    return el.held_expert_layer(
        x, p, experts_held=s["held"], expert_offset=0, top_k=s["top_k"],
        routed_scale=s["scale"], expert="relu2", tile=s["tile"])[0]


def timed(fn, *args, n=10):
    for _ in range(3):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--bias", default="0,0.5")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    s = TINY if args.tiny else CELL
    print("device", jax.devices()[0].device_kind, s)
    keys = iter(jax.random.split(jax.random.PRNGKey(args.seed), 8))
    draw = lambda *shape: 0.02 * jax.random.normal(   # noqa: E731
        next(keys), shape, jnp.float32)
    w = s["latent"]
    p = {"w_router": draw(s["d"], s["e"]), "s_up": draw(s["d"], s["fs"]),
         "s_down": draw(s["fs"], s["d"]), "w_fc1": draw(s["d"], w),
         "w_fc2": draw(w, s["d"]), "e_up": draw(s["held"], w, s["f"]),
         "e_down": draw(s["held"], s["f"], w)}
    x = jax.random.normal(next(keys), (s["t"], s["d"])).astype(jnp.bfloat16)
    grad = lambda fn: jax.jit(jax.grad(    # noqa: E731
        lambda x, p: fn(x, p).astype(jnp.float32).sum(), (0, 1)))
    for bias in [float(b) for b in args.bias.split(",")]:
        p["router_bias"] = jnp.where(jnp.arange(s["e"]) < s["held"], bias, 0.0)
        wide = jax.jit(lambda x, p: by_hand(x, p, s, False))(x, p)
        slots = jax.jit(lambda x, p: by_hand(x, p, s, True))(x, p)
        layer = jax.jit(lambda x, p: the_layer(x, p, s))(x, p)
        held = np.asarray(wide[2])
        a, b = (np.asarray(v[0].astype(jnp.float32)) for v in (wide, slots))
        differ = (a != b).any(axis=1)
        print(f"bias {bias}: rows held {int(held.sum())}, tokens holding "
              f"0 / 1 / 2 / 3 or more rows {[int((held == n).sum()) for n in (0, 1, 2)] + [int((held > 2).sum())]}; "
              f"the two buffers EQUAL {bool((wide[1] == slots[1]).all())}, "
              f"the rows held EQUAL {bool((wide[2] == slots[2]).all())}, the "
              f"layer's output EQUAL its compacted parts' "
              f"{bool((layer == slots[0]).all())}; outputs [T, k] against "
              f"[T, held]: {int((a != b).sum())} of {a.size} elements differ "
              f"in {int(differ.sum())} tokens, of them holding 3 or more rows "
              f"{int((differ & (held > 2)).sum())}, largest difference "
              f"{float(np.abs(a - b).max())} of {float(np.abs(a).max())}")
        ms = {name: timed(grad(fn), x, p) for name, fn in (
            ("[T, k] by hand", lambda x, p: by_hand(x, p, s, False)[0]),
            ("[T, held] by hand", lambda x, p: by_hand(x, p, s, True)[0]),
            ("held_expert_layer", lambda x, p: the_layer(x, p, s)))}
        pairs = s["t"] * (s["top_k"] - s["held"])
        print(f"  forward + backward, ms: {ms}; "
              f"{1e6 * (ms['[T, k] by hand'] - ms['[T, held] by hand']) / pairs:.1f}"
              f" ns a pair taken out of the pair domain ({pairs} pairs)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
