#!/usr/bin/env python3
"""ONE expert layer at a cell's shape on the chip.

    chiprun -- python3 scripts/pair_domain_chip.py [--bias 0,0.5]
        [--shape nemotron3super,keyevl2,qwen3next] [--other <tree>]
    python3 scripts/pair_domain_chip.py --tiny          # walks it here

PR 57, at ``nemotron3super_train_s8192``'s shape (16 384 tokens of 4096 in
bfloat16, 8 of 512 squared-ReLU experts of 1024 x 2688 in a latent of 1024, a
shared expert of 5376, sigmoid top 22): the pair domain compacted
(``held_expert_layer`` as it is: a token's held choices in ``experts_held``
slots) against the [T, k] pair domain every layer had before, composed by
hand from the layer's own parts. Weights drawn from ``--seed``; ``--bias b``
adds b to the held experts' selection bias, so that more tokens hold several
rows. For each bias it says whether the two row buffers' FILLED rows are EQUAL
(since PR 62 a padding row holds a copy of the last token's row and not zeros:
its zero is its weight, which the by-hand layer gives ``_mlp`` as the layer
does, ``pairs_to_rows``), in how many elements and by how much the two outputs
differ (the sum over a token's rows adds the same terms in another tree; a
token that holds under three rows cannot differ), by the rows a token holds,
and what forward + backward of the layer takes either way, with the
difference a pair taken out of the pair domain (ns a row: ROADMAP A15(1)(a)
asks that a row gather be timed against this).

PR 62, ``--other <tree>``: ``held_expert_layer`` of another tree (its
``ops/expert_layer.py`` loaded beside this one's) on the same inputs at each
``--shape`` (also ``keyevl2_train_s16384``'s: 16 of 128 gated experts of
2048 x 768, softmax top 8, no shared expert; ``qwen3next_train_s8192``'s: 32 of
512 of 2048 x 512, softmax top 10, a gated shared expert;
``kanana2_train_s8192``'s: 16 of 128 of 2048 x 768, sigmoid top 6, two shared
experts; ``xing4_train_s4096``'s: 8192 tokens, 8 of 64 of 3584 x 1024, sigmoid
top 4, a shared expert; ``lfm2moe_train_s8192``'s: 8 of 32 of 2048 x 1792,
sigmoid top 4, no shared expert; PR 68, ``kimilinear_train_s8192``'s: 8 of 256
of 2304 x 1024, sigmoid top 8, a shared expert): whether output and every
gradient are EQUAL, and forward + backward of either, this, other, other, this.

PR 65: where a token's slots are not whole tiles of 8 the pairs are named
choice-major (``choice * T + token``, ``sort_rows``' tables [k, T],
``at.slot_axis`` 0); ``--other <parent tree>`` also says whether the way INTO the
buffer is the other tree's element by element (the row buffer, the rows'
weights, ``tile_expert``, ``n_used``: each tree's own ``route``, ``sort_rows``,
``tokens_to_rows`` and ``pairs_to_rows`` on the same inputs), which is what
makes the change a renaming, and how far output and gradients are apart where
they are not EQUAL (the sum over a token's slots adds the same f32 terms,
over another axis). A script, not a metric."""
import argparse
import importlib.util
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from ray_tpu.ops import expert_layer as el  # noqa: E402

SHAPES = {
    "nemotron3super": dict(t=16384, d=4096, latent=1024, e=512, held=8,
                           f=2688, fs=5376, top_k=22, scale=5.0,
                           tile=el.ROW_TILE, expert="relu2", score="sigmoid"),
    "keyevl2": dict(t=16384, d=2048, latent=0, e=128, held=16, f=768, fs=0,
                    top_k=8, scale=1.0, tile=el.ROW_TILE, expert="swiglu",
                    score="softmax"),
    "qwen3next": dict(t=16384, d=2048, latent=0, e=512, held=32, f=512,
                      fs=512, top_k=10, scale=1.0, tile=el.ROW_TILE,
                      expert="swiglu", score="softmax", shared_gate=True),
    "kanana2": dict(t=16384, d=2048, latent=0, e=128, held=16, f=768,
                    fs=1536, top_k=6, scale=2.448, tile=el.ROW_TILE,
                    expert="swiglu", score="sigmoid"),
    "xing4": dict(t=8192, d=3584, latent=0, e=64, held=8, f=1024, fs=1024,
                  top_k=4, scale=2.0, tile=el.ROW_TILE, expert="swiglu",
                  score="sigmoid"),
    "lfm2moe": dict(t=16384, d=2048, latent=0, e=32, held=8, f=1792, fs=0,
                    top_k=4, scale=1.0, tile=el.ROW_TILE, expert="swiglu",
                    score="sigmoid"),
    "kimilinear": dict(t=16384, d=2304, latent=0, e=256, held=8, f=1024,
                       fs=1024, top_k=8, scale=2.446, tile=el.ROW_TILE,
                       expert="swiglu", score="sigmoid"),
}
TINY = dict(t=256, d=64, latent=32, e=32, held=8, f=48, fs=64, top_k=22,
            scale=5.0, tile=8, expert="relu2", score="sigmoid")


def load_other(tree: str):
    """``ops/expert_layer.py`` of another tree as a module of THIS tree's
    package (its relative imports find this tree's modules)."""
    spec = importlib.util.spec_from_file_location(
        "ray_tpu.ops.expert_layer_other",
        os.path.join(tree, "ray_tpu", "ops", "expert_layer.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def by_hand(x, p, s, compact):
    """The layer from its parts, on [T, k] or, ``compact``, on [T, held] ->
    (output, the row buffer, rows a token holds, which rows hold a pair)."""
    dt = x.dtype
    rows = el.buffer_rows(s["t"], s["top_k"], s["held"], s["tile"])
    weights, chosen = el.route(x, p["w_router"], p["router_bias"],
                               top_k=s["top_k"], routed_scale=s["scale"])
    if compact:
        weights, chosen = el.compact_held(weights, chosen, s["held"], 0)
    at = el.sort_rows(chosen, s["held"], 0, rows, s["tile"])
    buf = el.tokens_to_rows(jnp.dot(x, p["w_fc1"].astype(dt)), at)
    # over the row buffer ``_mlp`` MUST carry the rows' weights: a padding
    # row is a copy of a token's row and its weight's 0 is what zeroes it
    y = el._mlp("relu2", buf, p, "e",
                lambda a, w: el.grouped_matmul(a, w, at["tile_expert"],
                                               at["n_used"], s["tile"]),
                el.pairs_to_rows(weights, at))
    routed = jnp.dot(el.rows_to_tokens(y, at), p["w_fc2"].astype(dt))
    return (el._mlp("relu2", x, p, "s", jnp.dot) + routed, buf,
            jnp.sum(at["pair_held"], axis=at.slot_axis),
            at["row_pair"] < chosen.size)


def the_layer(x, p, s, module=el):
    return module.held_expert_layer(
        x, p, experts_held=s["held"], expert_offset=0, top_k=s["top_k"],
        routed_scale=s["scale"], expert=s["expert"], score=s["score"],
        tile=s["tile"])[0]


def way_in(x, p, s, module):
    """``module``'s way into the row buffer, as its ``held_expert_layer``
    goes: (the buffer, the rows' weights, ``tile_expert``, ``n_used``)."""
    weights, chosen = module.route(
        x, p["w_router"], p.get("router_bias"), top_k=s["top_k"],
        routed_scale=s["scale"], score=s["score"])
    if s["top_k"] > s["held"]:
        weights, chosen = module.compact_held(weights, chosen, s["held"], 0)
    at = module.sort_rows(
        chosen, s["held"], 0,
        module.buffer_rows(s["t"], s["top_k"], s["held"], s["tile"]),
        s["tile"])
    u = jnp.dot(x, p["w_fc1"].astype(x.dtype)) if s["latent"] else x
    return (module.tokens_to_rows(u, at), module.pairs_to_rows(weights, at),
            at["tile_expert"], at["n_used"])


def draw_layer(s, seed):
    """(x [t, d] bfloat16, the layer's parameters in float32) of shape s."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 12))
    draw = lambda *shape: 0.02 * jax.random.normal(   # noqa: E731
        next(keys), shape, jnp.float32)
    w = s["latent"] or s["d"]
    p = {"w_router": draw(s["d"], s["e"]),
         "e_up": draw(s["held"], w, s["f"]),
         "e_down": draw(s["held"], s["f"], w)}
    if s["fs"]:
        p.update(s_up=draw(s["d"], s["fs"]), s_down=draw(s["fs"], s["d"]))
    if s["expert"] == "swiglu":
        p["e_gate"] = draw(s["held"], w, s["f"])
        if s["fs"]:
            p["s_gate"] = draw(s["d"], s["fs"])
    if s.get("shared_gate"):
        p["s_gate_w"] = draw(s["d"], 1)
    if s["latent"]:
        p.update(w_fc1=draw(s["d"], w), w_fc2=draw(w, s["d"]))
    if s["score"] == "sigmoid":
        p["router_bias"] = jnp.zeros((s["e"],))
    x = jax.random.normal(next(keys), (s["t"], s["d"])).astype(jnp.bfloat16)
    return x, p


def timed(fn, *args, n=10):
    for _ in range(3):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n


def grad_of(fn):
    return jax.jit(jax.grad(
        lambda x, p: fn(x, p).astype(jnp.float32).sum(), (0, 1)))


def pair_domains(x, p, s, biases):
    """PR 57's comparison: [T, k] by hand against [T, held]."""
    for bias in biases:
        p["router_bias"] = jnp.where(jnp.arange(s["e"]) < s["held"], bias, 0.0)
        wide = jax.jit(lambda x, p: by_hand(x, p, s, False))(x, p)
        slots = jax.jit(lambda x, p: by_hand(x, p, s, True))(x, p)
        layer = jax.jit(lambda x, p: the_layer(x, p, s))(x, p)
        held = np.asarray(wide[2])
        a, b = (np.asarray(v[0].astype(jnp.float32)) for v in (wide, slots))
        differ = (a != b).any(axis=1)
        filled = np.asarray(wide[3])
        same_rows = bool((filled == np.asarray(slots[3])).all())
        print(f"bias {bias}: rows held {int(held.sum())}, tokens holding "
              f"0 / 1 / 2 / 3 or more rows {[int((held == n).sum()) for n in (0, 1, 2)] + [int((held > 2).sum())]}; "
              f"the same rows FILLED {same_rows} and the two buffers EQUAL "
              f"there {bool((np.asarray(wide[1])[filled] == np.asarray(slots[1])[filled]).all())}, "
              f"the rows held EQUAL {bool((wide[2] == slots[2]).all())}, the "
              f"layer's output EQUAL its compacted parts' "
              f"{bool((layer == slots[0]).all())}; outputs [T, k] against "
              f"[T, held]: {int((a != b).sum())} of {a.size} elements differ "
              f"in {int(differ.sum())} tokens, of them holding 3 or more rows "
              f"{int((differ & (held > 2)).sum())}, largest difference "
              f"{float(np.abs(a - b).max())} of {float(np.abs(a).max())}")
        ms = {name: timed(grad_of(fn), x, p) for name, fn in (
            ("[T, k] by hand", lambda x, p: by_hand(x, p, s, False)[0]),
            ("[T, held] by hand", lambda x, p: by_hand(x, p, s, True)[0]),
            ("held_expert_layer", lambda x, p: the_layer(x, p, s)))}
        pairs = s["t"] * (s["top_k"] - s["held"])
        print(f"  forward + backward, ms: {ms}; "
              f"{1e6 * (ms['[T, k] by hand'] - ms['[T, held] by hand']) / pairs:.1f}"
              f" ns a pair taken out of the pair domain ({pairs} pairs)")


def two_trees(x, p, s, other):
    """PR 62's comparison: this tree's layer and another tree's."""
    grads = {"this": grad_of(lambda x, p: the_layer(x, p, s)),
             "other": grad_of(lambda x, p: the_layer(x, p, s, other))}
    outs = {k: jax.jit(lambda x, p, m=m: the_layer(x, p, s, m))(x, p)
            for k, m in (("this", el), ("other", other))}
    got = {k: g(x, p) for k, g in grads.items()}
    same = {"y": bool((outs["this"] == outs["other"]).all()),
            "x": bool((got["this"][0] == got["other"][0]).all())}
    same.update({n: bool((got["this"][1][n] == got["other"][1][n]).all())
                 for n in p})
    finite = all(bool(jnp.isfinite(v.astype(jnp.float32)).all())
                 for v in jax.tree.leaves((outs["this"], got["this"])))
    rows = el.buffer_rows(s["t"], s["top_k"], s["held"], s["tile"])
    print(f"  this tree against {other.__file__}: output and gradients EQUAL "
          f"{all(same.values())} {same}, all finite {finite}")

    def apart(a, b):
        """(largest difference over the other's largest entry, share of
        elements that differ)."""
        a, b = (np.asarray(v.astype(jnp.float32)) for v in (a, b))
        return [float(np.abs(a - b).max() / np.abs(b).max()),
                float((a != b).mean())]

    print("  where not EQUAL, [largest difference of the other's largest "
          "entry, share of elements]:", {
              n: apart(a, b) for n, a, b in [
                  ("y", outs["this"], outs["other"]),
                  ("x", got["this"][0], got["other"][0])] + [
                  (n, got["this"][1][n], got["other"][1][n]) for n in p]
              if not same[n]})
    ways = [jax.jit(lambda x, p, m=m: way_in(x, p, s, m))(x, p)
            for m in (el, other)]
    print("  the way into the buffer EQUAL the other tree's, element by "
          "element:", {n: bool((a == b).all()) for n, a, b in zip(
              ("buffer", "row_weight", "tile_expert", "n_used"), *ways)},
          f"({int(ways[0][3][0])} tiles used of {rows // s['tile']}, "
          f"{int((ways[0][1] != 0).sum())} rows weighted)")
    ms = [(k, timed(grads[k], x, p)) for k in ("this", "other", "other",
                                               "this")]
    this, that = (min(v for k, v in ms if k == name)
                  for name in ("this", "other"))
    print(f"  forward + backward, ms, in the order run: {ms}; other - this "
          f"{that - this:.3f} ms = {1e6 * (that - this) / rows:.2f} ns a row "
          f"of the buffer ({rows} rows of {s['latent'] or s['d']})")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--bias", default="0,0.5")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shape", default="nemotron3super")
    ap.add_argument("--other", default="", help="a second tree's root")
    args = ap.parse_args()
    other = load_other(args.other) if args.other else None
    shapes = {"tiny": TINY} if args.tiny else {
        name: SHAPES[name] for name in args.shape.split(",")}
    for name, s in shapes.items():
        print("device", jax.devices()[0].device_kind, name, s)
        x, p = draw_layer(s, args.seed)
        if other is not None:
            two_trees(x, p, s, other)
        elif s["top_k"] > s["held"]:
            pair_domains(x, p, s, [float(b) for b in args.bias.split(",")])
    return 0


if __name__ == "__main__":
    sys.exit(main())
