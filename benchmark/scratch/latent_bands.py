#!/usr/bin/env python3
"""The two LATENT flash kernels a causal call runs (``flash_latent_fwd`` and
the fused backward, launched as ``flash_latent_bwd_dkv``) alone on the chip,
at both cells' shapes, by the height of the causal row bands a program works
in a step the diagonal crosses (PR 46):

    python3 benchmark/scratch/latent_bands.py <tree> [--parent <tree>]
        [--heights 256,128,0] [--calls 6] [--out <dir>] [--f32] [--tiny]
        [--describe]

For each shape (kanana2_train_s8192's B2 S8192 H32 and xing4_train_s4096's
B2 S4096 H32 with YaRN's scale handed in; blocks of 1024) the parent tree's
kernels as they stand, then the change's with its ``_latent_band`` replaced
by each height in turn (0 = the whole block under the mask, the parent's
form; the first height is the rule's own and is left as the module has it).
Each variant's forward and backward are traced and lowered twice (the
smaller wall time is ``trace_lower_s``: the Python a band costs at every
start of a process), compiled once, run, and compared with the PARENT's o
and five gradients element by element (``band_heights.py`` and
``latent_equal.py`` are the models): share of equal elements, share within
one bf16 step, largest difference. Then all variants run ``--calls`` times
under ONE ``jax.profiler`` trace; the kernels' events are found by their
pinned names and split in order of time. ``ms_a_step`` is five layers'
forward + backward, what either cell's step runs. ``--f32`` also holds the
parent and the rule's height to ``latent_equal.reference`` (float32, a
head at a time) at the shape with the default scale. ``--describe``
compiles every variant for a described v5e and runs nothing (no chip);
``--tiny`` walks the script here in interpret mode. One JSON object on
stdout.
"""
import argparse
import json
import os
import sys
import time

# (B, S, H, sm_scale): the cells' calls; None = 1 / sqrt(192)
SHAPES = [("kanana2_train_s8192", 2, 8192, 32, None),
          ("xing4_train_s4096", 2, 4096, 32, 0.1447)]
TINY = [("tiny", 1, 512, 4, None)]
DN, DR, DV = 128, 64, 128
NAMES = ("o", "dq_nope", "dq_rope", "dk_nope", "dk_rope", "dv")
LAYERS = 5


def bf16_steps(a, c):
    """|a - c| in steps of bf16's grid (sign-magnitude bits made monotone;
    +0 and -0 coincide)."""
    import jax
    import jax.numpy as jnp

    def key(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.int32)
        mag = bits & 0x7FFF
        return jnp.where(bits & 0x8000, -mag, mag)

    return jnp.abs(key(a) - key(c))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("tree")
    ap.add_argument("--parent")
    ap.add_argument("--heights", default="256,128,0")
    ap.add_argument("--calls", type=int, default=6)
    ap.add_argument("--out", default="chiprun_out/latent_bands")
    ap.add_argument("--f32", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--describe", action="store_true")
    args = ap.parse_args()

    if args.describe:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp

    scratch = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [os.path.dirname(os.path.dirname(scratch)), scratch]
    from benchmark.lib import trace as T
    from kernel_equal import load     # a tree's module, imported anew
    from latent_equal import reference

    block = 256 if args.tiny else 1024
    heights = [int(h) for h in args.heights.split(",")]
    if args.tiny:
        heights = [128, 0]
    mods = []
    if args.parent:
        mods.append(("parent", load(os.path.abspath(args.parent))[0], [None]))
    chg = load(os.path.abspath(args.tree))[0]
    mods.append(("change", chg, heights))
    rule = chg._latent_band
    sharding = None
    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
        for _, mod, _ in mods:
            mod._use_interpret = lambda: False

    bf16, f32 = jnp.bfloat16, jnp.float32
    plan, res = [], {"calls": args.calls, "block": block, "variants": {}}
    if not args.describe:
        res["device"] = jax.devices()[0].device_kind
    for cell, b, s, h, scale in (TINY if args.tiny else SHAPES):
        sm = (DN + DR) ** -0.5 if scale is None else scale
        shapes = [(b, s, h * DN), (b, s, h * DR), (b, s, h * DN), (b, s, DR),
                  (b, s, h * DV), (b, s, h * DV)]
        if args.describe:
            qn, qr, kn, k_rope, v, g = [jax.ShapeDtypeStruct(
                x, bf16, sharding=sharding) for x in shapes]
            kr = jax.ShapeDtypeStruct((b, s, 128), bf16, sharding=sharding)
            o = v
            lse = jax.ShapeDtypeStruct((b * h, 1, s), f32, sharding=sharding)
        else:
            keys = jax.random.split(jax.random.PRNGKey(46 + s), 6)
            qn, qr, kn, k_rope, v, g = [
                jax.random.normal(k, x, f32).astype(bf16)
                for k, x in zip(keys, shapes)]
            kr = jnp.tile(k_rope, (1, 1, 128 // DR))
        base = None
        for tag, mod, hs in mods:
            for i, hb in enumerate(hs):
                if tag == "change":
                    # the first height is the module's own rule, untouched
                    mod._latent_band = rule if i == 0 else (
                        lambda hb: lambda *a: hb)(hb)
                    assert i or rule(True, block, block) == hb, (
                        rule(True, block, block), hb)
                label = f"{cell}/{tag}/band{'-' if hb is None else hb}"
                row = res["variants"].setdefault(label, {})

                def fwd(qn, qr, kn, kr, v, mod=mod):
                    return mod._latent_fwd(qn, qr, kn, kr, v, h, sm, True,
                                           block, block)

                def bwd(qn, qr, kn, kr, v, o, lse, g, mod=mod):
                    return mod._latent_bwd(qn, qr, kn, kr, v, o, lse, g, h,
                                           sm, True, block, block)

                def lowered(fn, *a):
                    best = None
                    for _ in range(2):
                        t0 = time.perf_counter()
                        # a new function each time: nothing traced is reused
                        low = jax.jit(lambda *x: fn(*x)).lower(*a)
                        dt = time.perf_counter() - t0
                        best = dt if best is None else min(best, dt)
                    return low, best
                try:
                    low_f, t_f = lowered(fwd, qn, qr, kn, kr, v)
                    cf = low_f.compile()
                    if not args.describe:
                        o, lse = cf(qn, qr, kn, kr, v)
                    low_b, t_b = lowered(bwd, qn, qr, kn, kr, v, o, lse, g)
                    cb = low_b.compile()
                except Exception as e:  # noqa: BLE001 — e.g. out of VMEM
                    row["refused"] = str(e)[-400:]
                    continue
                row["trace_lower_s"] = {"fwd": t_f, "bwd": t_b}
                if args.describe:
                    row["compiled"] = True
                    continue
                dqn, dqr, dkn, dkr, dv = cb(qn, qr, kn, kr, v, o, lse, g)
                # d(kr) [B, S, 128]: lane block j holds the heads that
                # read it; the shared key's gradient is their sum
                got = (o, dqn, dqr, dkn, dkr, dv)
                if base is None:
                    base = got
                row["against_" + ("parent" if args.parent else "first")] = {
                    n: {"share_equal": float((a == c).mean()),
                        "share_within_one_bf16_step": float(
                            (bf16_steps(a, c) <= 1).mean()),
                        "max_bf16_steps": int(bf16_steps(a, c).max()),
                        "max_abs": float(jnp.abs(a.astype(f32)
                                                 - c.astype(f32)).max())}
                    for n, a, c in zip(NAMES, base, got)}
                if args.f32 and scale is None and i == 0:
                    heads = lambda x: x.reshape(b, s, h, -1)  # noqa: E731
                    ro, rq, rk, rv, rqr, rkr = jax.jit(reference)(
                        heads(qn), heads(kn), heads(v), heads(qr), k_rope,
                        heads(g))
                    want = (ro, rq, rqr, rk, rkr, rv)
                    mine = (heads(o), heads(dqn), heads(dqr), heads(dkn),
                            dkr.reshape(b, s, 128 // DR, DR).sum(2),
                            heads(dv))
                    row["against_f32"] = {
                        n: {"rms_error": float(jnp.sqrt(jnp.mean(
                            (a.astype(f32) - r) ** 2))),
                            "rms_f32": float(jnp.sqrt(jnp.mean(r ** 2)))}
                        for n, a, r in zip(NAMES, mine, want)}
                plan.append((label, "flash_latent_fwd", cf,
                             (qn, qr, kn, kr, v)))
                plan.append((label, "flash_latent_bwd_dkv", cb,
                             (qn, qr, kn, kr, v, o, lse, g)))
        chg._latent_band = rule
    if args.describe:
        print(json.dumps(res, indent=1))
        return 0

    os.makedirs(args.out, exist_ok=True)
    wall = {}
    jax.profiler.start_trace(args.out)
    for label, kind, fn, ops in plan:
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = fn(*ops)
        jax.block_until_ready(out)
        wall[(label, kind)] = (time.perf_counter() - t0) / args.calls
    jax.profiler.stop_trace()

    path = T.find_xplane(args.out)
    tr = T.load_xplane(path) if path else None
    for kind in ("flash_latent_fwd", "flash_latent_bwd_dkv"):
        ev = sorted(T.ops_matching(tr, kind), key=lambda e: e[1]) \
            if tr is not None and tr.devices else []
        mine = [lab for lab, kd, _, _ in plan if kd == kind]
        for i, label in enumerate(mine):
            row = res["variants"][label].setdefault("ms_a_call", {})
            row[kind + "_wall"] = 1e3 * wall[(label, kind)]
            if len(ev) == len(mine) * args.calls:
                chunk = ev[i * args.calls:(i + 1) * args.calls]
                row[kind] = 1e3 * sum(e[2] for e in chunk) / args.calls
    for row in res["variants"].values():
        ms = row.get("ms_a_call", {})
        sfx = "" if len(ms) == 4 else "_wall"
        if ms:
            row["ms_a_step" + sfx] = LAYERS * (
                ms["flash_latent_fwd" + sfx] + ms["flash_latent_bwd_dkv" + sfx])
    print(json.dumps(res, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
