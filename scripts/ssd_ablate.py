#!/usr/bin/env python3
"""Where the state-space scan kernels' time goes: PR 38's two kernel bodies
(``ray_tpu/ops/ssd_scan.py`` as of commit 41e7050, copied here) timed on the
chip with one part taken out each, kernels alone, forward and backward
apart, at ``granite4h_train_s4096``'s shape (B 2, T 4096, 64 heads of 64,
N 128, chunk 256):

    python3 scripts/ssd_ablate.py [--calls 20] [--only bwd:tail,fwd:whole]
        [--tiny]

The parts (ISSUE 40, step 1):

    whole     nothing taken out: the parent's body
    tail      backward only: d(cumulative sum) and d(dt) written as zeros
              (no head sums, no pairs, no ``mine``, no transposes back)
    columns   the per-head columns (exp(c), exp(c_Q - c), dt, exp(c_Q)) and
              their lane and row broadcasts replaced by constants; the
              decay tile keeps its [Q, Q] chain on a column made of iota
    decay     the decay tile is 1 and nothing multiplies by it or by g
    state     the products with the state of a tile gone (five in the
              backward, two in the forward)
    big       the [Q, Q]-by-128 products of a head gone (two in the
              backward, one in the forward)
    accum     backward only: dB and dC summed over the tiles in values and
              added to the resident block once a program
    masks     every lane, row and ``end`` mask made once at the top of the
              program (a CORRECT variant: whether Mosaic already shares
              them)
    empty     the grid and its copies alone: x (and dy) read, y / dx
              written from them, the other outputs as zeros

All but ``whole``, ``accum`` and ``masks`` compute wrong numbers: they live
in this script only. One JSON object on stdout; ``--tiny`` walks the same
code here in interpret mode. PR 40; a script, not a metric."""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from ray_tpu.ops.flash_attention import (  # noqa: E402
    _AB, _ABT, _ATB, _LANES, _dot, _head_lanes)

INTERPRET = False    # set by main: interpreted wherever there is no TPU

PARTS = {
    "fwd": ("whole", "columns", "decay", "state", "big", "masks", "empty"),
    "bwd": ("whole", "tail", "columns", "decay", "state", "big", "accum",
            "masks", "tail+columns+accum", "empty"),
}


# ---- the parent's helpers, as they were -----------------------------------


def _group_scores(c, b):
    g = _dot(c, b, _ABT)
    rows = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    return jnp.where(rows >= cols, g, 0.0)


def _head_vectors(dt_ref, cum_ref, k):
    cr = cum_ref[k:k + 1, :]
    cc = cr.T
    last = jax.lax.broadcasted_iota(jnp.int32, cc.shape, 0) == cc.shape[0] - 1
    cq = jnp.sum(jnp.where(last, cc, 0.0), axis=0, keepdims=True)
    return cr, cc, dt_ref[k:k + 1, :].T, cq


def _decay(cc, cr):
    return jnp.exp(jnp.minimum(cc - cr, 0.0))


def _by_lanes(cols, p, first=None):
    shape = (cols[0].shape[0], _LANES)
    out = jnp.broadcast_to(cols[0], shape)
    if len(cols) == 2:
        if first is None:
            first = jax.lax.broadcasted_iota(jnp.int32, shape, 1) < p
        out = jnp.where(first, out, jnp.broadcast_to(cols[1], shape))
    return out


def _by_rows(vals, p, n, first=None):
    shape = (_LANES, n)
    out = jnp.broadcast_to(vals[0], shape)
    if len(vals) == 2:
        if first is None:
            first = jax.lax.broadcasted_iota(jnp.int32, shape, 0) < p
        out = jnp.where(first, out, jnp.broadcast_to(vals[1], shape))
    return out


class _Masks:
    """The masks of a program made once (``masks``), or at every use as the
    parent makes them."""

    def __init__(self, once: bool, q: int, p: int, n: int):
        self.p = p
        per_tile = _LANES // p
        if once and per_tile == 2:
            lane = jax.lax.broadcasted_iota(jnp.int32, (q, _LANES), 1)
            row = jax.lax.broadcasted_iota(jnp.int32, (_LANES, n), 0)
            self.lane_first = lane < p
            self.row_first = row < p
        else:
            self.lane_first = self.row_first = None
        self.end = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1

    def head(self, x, j):
        if self.lane_first is None or x.shape != self.lane_first.shape:
            return _head_lanes(x, j, self.p)
        keep = self.lane_first if j == 0 else ~self.lane_first
        return jnp.where(keep, x, jnp.zeros_like(x))

    def head_sum(self, x, j):
        return jnp.sum(self.head(x, j), axis=1, keepdims=True)

    def rows(self, x, j, per_tile):
        if per_tile == 1:
            return x
        if self.row_first is None:
            row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
            return jnp.where((row >= j * self.p) & (row < (j + 1) * self.p),
                             x, 0.0)
        return jnp.where(self.row_first if j == 0 else ~self.row_first,
                         x, 0.0)


def _columns(dt_ref, cum_ref, k, cut):
    """(row, column, dt column, last) of head k; with ``columns`` cut the
    column is made of iota (no transpose, no masked sum) and the rest are
    plain numbers."""
    if "columns" not in cut:
        return _head_vectors(dt_ref, cum_ref, k)
    cr = cum_ref[k:k + 1, :]
    q = cr.shape[1]
    cc = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0).astype(
        jnp.float32) * -1e-3
    return cr, cc, None, None


# ---- forward ---------------------------------------------------------------


def _fwd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, y_ref, st_ref,
                h_scr, g_scr, *, p, cut):
    ci, hb = pl.program_id(1), pl.program_id(2)
    per_tile = _LANES // p
    dtype = x_ref.dtype
    q, n = x_ref.shape[0], b_ref.shape[1]

    @pl.when(ci == 0)
    def _first_chunk():
        h_scr[hb] = jnp.zeros(h_scr.shape[1:], h_scr.dtype)

    @pl.when(hb == 0)
    def _first_block():
        g_scr[...] = _group_scores(c_ref[...], b_ref[...])

    st_ref[...] = h_scr[hb]
    if "empty" in cut:
        y_ref[...] = x_ref[...]
        return
    g = g_scr[...]
    bm, cm = b_ref[...], c_ref[...]
    m = _Masks("masks" in cut, q, p, n)
    for i in range(x_ref.shape[1] // _LANES):
        lanes = pl.ds(i * _LANES, _LANES)
        xt = x_ref[:, lanes]
        h0 = h_scr[hb, lanes, :]
        y, into, carry, last = None, [], [], []
        for j in range(per_tile):
            cr, cc, dc, cq = _columns(dt_ref, cum_ref, i * per_tile + j, cut)
            xj = m.head(xt, j).astype(jnp.float32)
            xd = (xj * (0.01 if dc is None else dc)).astype(dtype)
            if "big" in cut:
                part = xd.astype(jnp.float32)
            elif "decay" in cut:
                part = _dot(g.astype(dtype), xd, _AB)
            else:
                part = _dot((g * _decay(cc, cr)).astype(dtype), xd, _AB)
            y = part if y is None else y + part
            if dc is not None:
                into.append(jnp.exp(cc))
                carry.append(jnp.exp(cq - cc) * dc)
                last.append(jnp.exp(cq))
        if "columns" in cut:
            ec, sw, eq = 0.9, 0.008, 0.7
        else:
            ec, sw = _by_lanes(into, p, m.lane_first), \
                _by_lanes(carry, p, m.lane_first)
            eq = _by_rows(last, p, n, m.row_first)
        xw = (xt.astype(jnp.float32) * sw).astype(dtype)
        if "state" in cut:
            h_scr[hb, lanes, :] = eq * h0
        else:
            y = y + ec * _dot(cm, h0.astype(dtype), _ABT)
            h_scr[hb, lanes, :] = eq * h0 + _dot(xw, bm, _ATB)
        y_ref[:, lanes] = y.astype(y_ref.dtype)


# ---- backward --------------------------------------------------------------


def _bwd_kernel(x_ref, dy_ref, dt_ref, cum_ref, b_ref, c_ref, st_ref,
                dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref,
                dh_scr, g_scr, dg_scr, *, p, cut):
    ci, hb = pl.program_id(1), pl.program_id(2)
    per_tile = _LANES // p
    dtype = x_ref.dtype
    q, n = x_ref.shape[0], b_ref.shape[1]

    @pl.when(ci == 0)
    def _last_chunk():
        dh_scr[hb] = jnp.zeros(dh_scr.shape[1:], dh_scr.dtype)

    @pl.when(hb == 0)
    def _first_block():
        g_scr[...] = _group_scores(c_ref[...], b_ref[...])
        dg_scr[...] = jnp.zeros_like(dg_scr)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    if "empty" in cut:
        dx_ref[...] = x_ref[...] + dy_ref[...]
        ddt_ref[...] = jnp.zeros_like(ddt_ref)
        dcum_ref[...] = jnp.zeros_like(dcum_ref)
        return
    g = g_scr[...]
    bm, cm = b_ref[...], c_ref[...]
    m = _Masks("masks" in cut, q, p, n)
    dg = None
    dbs = dcs = None
    if "tail" in cut:
        ddt_ref[...] = jnp.zeros_like(ddt_ref)
        dcum_ref[...] = jnp.zeros_like(dcum_ref)
    for i in range(x_ref.shape[1] // _LANES):
        lanes = pl.ds(i * _LANES, _LANES)
        xt, dyt = x_ref[:, lanes], dy_ref[:, lanes]
        xf, dyf = xt.astype(jnp.float32), dyt.astype(jnp.float32)
        h0, dh = st_ref[lanes, :], dh_scr[hb, lanes, :]
        dxd, pairs, into, carry, step, last = None, [], [], [], [], []
        for j in range(per_tile):
            cr, cc, dc, cq = _columns(dt_ref, cum_ref, i * per_tile + j, cut)
            dyj = m.head(dyt, j)
            xd = (m.head(xf, j) * (0.01 if dc is None else dc)).astype(dtype)
            if "big" in cut:
                # a [Q, Q] tile of the same making cost as a read of g
                u = g if "decay" in cut else g * _decay(cc, cr)
                part = dyj.astype(jnp.float32)
                gd = None
            elif "decay" in cut:
                u = _dot(dyj, xd, _ABT)
                gd = g
            else:
                decay = _decay(cc, cr)
                u = _dot(dyj, xd, _ABT) * decay
                gd = g * decay
            dg = u if dg is None else dg + u
            if "tail" not in cut:
                w = u * g
                pairs.append(jnp.sum(w, axis=1, keepdims=True)
                             - jnp.sum(w, axis=0, keepdims=True).T)
            if gd is not None:
                part = _dot(gd.astype(dtype), dyj, _ATB)
            dxd = part if dxd is None else dxd + part
            if dc is not None:
                into.append(jnp.exp(cc))
                carry.append(jnp.exp(cq - cc))
                step.append(dc)
                last.append(jnp.exp(cq))
        if "columns" in cut:
            ec, sw, dcl, eq = 0.9, 0.8, 0.01, 0.7
        else:
            ec, sw, dcl = (_by_lanes(v, p, m.lane_first)
                           for v in (into, carry, step))
            eq = _by_rows(last, p, n, m.row_first)
        h0m, dhm = h0.astype(dtype), dh.astype(dtype)
        e = dyf * ec
        em = e.astype(dtype)
        xw = xf * sw * dcl
        if "state" in cut:
            dxw = xf
            dh_scr[hb, lanes, :] = eq * dh
            hc = dyf
        else:
            dcp, dbp = _dot(em, h0m, _AB), _dot(xw.astype(dtype), dhm, _AB)
            if "accum" in cut:
                dcs = dcp if dcs is None else dcs + dcp
                dbs = dbp if dbs is None else dbs + dbp
            else:
                dc_ref[...] += dcp
                db_ref[...] += dbp
            dxw = _dot(bm, dhm, _ABT)
            dh_scr[hb, lanes, :] = _dot(em, cm, _ATB) + eq * dh
            hc = None
        dx_ref[:, lanes] = ((dxd + dxw * sw) * dcl).astype(dx_ref.dtype)
        if "tail" in cut:
            continue
        if hc is None:
            hc = _dot(cm, h0m, _ABT)
        through = e * hc
        written = dxw * xw
        dstep = (dxd + dxw * sw) * xf
        kept = dh * eq * h0
        for j in range(per_tile):
            k = i * per_tile + j
            wr = m.head_sum(written, j)
            mine = m.rows(kept, j, per_tile)
            dcq = jnp.sum(wr, axis=0, keepdims=True) + jnp.sum(
                jnp.sum(mine, axis=1, keepdims=True), axis=0, keepdims=True)
            dcum = pairs[j] + m.head_sum(through, j) - wr \
                + jnp.where(m.end, dcq, 0.0)
            dcum_ref[k:k + 1, :] = dcum.T
            ddt_ref[k:k + 1, :] = m.head_sum(dstep, j).T
    dg_scr[...] += dg
    if dcs is not None:
        dc_ref[...] += dcs
        db_ref[...] += dbs

    @pl.when(hb == pl.num_programs(2) - 1)
    def _last_block():
        rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
        dgm = jnp.where(rows >= cols, dg_scr[...], 0.0).astype(dtype)
        dc_ref[...] += _dot(dgm, bm, _AB)
        db_ref[...] += _dot(dgm, cm, _ATB)


# ---- the calls -------------------------------------------------------------


def _specs(b, t, h, p, n, chunk, hpb, reverse):
    nc = t // chunk
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    w = hpb * p
    return {
        "x": pl.BlockSpec((None, chunk, w), lambda b, c, k: (b, at(c), k)),
        "dt": pl.BlockSpec((None, hpb, chunk),
                           lambda b, c, k: (b, k, at(c))),
        "bc": pl.BlockSpec((None, chunk, n), lambda b, c, k: (b, at(c), 0)),
        "state": pl.BlockSpec((None, None, w, n),
                              lambda b, c, k: (b, at(c), k, 0)),
    }


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)


def fwd_call(x, dt_t, cum_t, bm, cm, *, p, chunk, hpb, cut):
    b, t, hp = x.shape
    h, n, nc = hp // p, bm.shape[-1], t // chunk
    s = _specs(b, t, h, p, n, chunk, hpb, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, cut=cut),
        grid=(b, nc, h // hpb),
        in_specs=[s["x"], s["dt"], s["dt"], s["bc"], s["bc"]],
        out_specs=[s["x"], s["state"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, nc, hp, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((h // hpb, hpb * p, n), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        compiler_params=_params(),
        name="ssd_ablate_fwd",
        interpret=INTERPRET,
    )(x, dt_t, cum_t, bm, cm)


def bwd_call(x, dy, dt_t, cum_t, bm, cm, states, *, p, chunk, hpb, cut):
    b, t, hp = x.shape
    h, n, nc = hp // p, bm.shape[-1], t // chunk
    s = _specs(b, t, h, p, n, chunk, hpb, reverse=True)
    rows = jax.ShapeDtypeStruct(dt_t.shape, jnp.float32)
    shared = jax.ShapeDtypeStruct(bm.shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, cut=cut),
        grid=(b, nc, h // hpb),
        in_specs=[s["x"], s["x"], s["dt"], s["dt"], s["bc"], s["bc"],
                  s["state"]],
        out_specs=[s["x"], s["dt"], s["dt"], s["bc"], s["bc"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), rows, rows,
                   shared, shared],
        scratch_shapes=[pltpu.VMEM((h // hpb, hpb * p, n), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        compiler_params=_params(),
        name="ssd_ablate_bwd",
        interpret=INTERPRET,
    )(x, dy, dt_t, cum_t, bm, cm, states)


def inputs(b, t, h, p, n, chunk):
    """The kernels' own arguments, as ``ssd_scan`` hands them: merged
    [B, T, H*P] bf16, dt and its cumulative sum inside a chunk [B, H, T]."""
    import numpy as np

    ks = jax.random.split(jax.random.PRNGKey(40), 6)
    bf = jnp.bfloat16
    x = jax.random.normal(ks[0], (b, t, h * p)).astype(bf)
    dy = jax.random.normal(ks[5], (b, t, h * p)).astype(bf)
    dt = jnp.exp(jax.random.uniform(ks[1], (b, t, h), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    a = -jnp.arange(1, h + 1, dtype=jnp.float32) * 64 / h
    cum = jnp.cumsum((dt * a).reshape(b, t // chunk, chunk, h), axis=2)
    rows = lambda v: jnp.swapaxes(v.reshape(b, t, h), 1, 2)  # noqa: E731
    bm = (jax.random.normal(ks[2], (b, t, n)) * 0.5).astype(bf)
    cm = (jax.random.normal(ks[3], (b, t, n)) * 0.5).astype(bf)
    return x, dy, rows(dt), rows(cum), bm, cm


def describe(args) -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    b, t, h, p, n, chunk = 2, 4096, 64, 64, 128, args.chunk
    sd = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=chip)
    f32 = jnp.float32
    x, rows, bc = sd((b, t, h * p)), sd((b, h, t), f32), sd((b, t, n))
    states = sd((b, t // chunk, h * p, n), f32)
    kw = dict(p=p, chunk=chunk, hpb=16)
    only = set(filter(None, args.only.split(",")))
    for side, parts in PARTS.items():
        for part in parts:
            if only and f"{side}:{part}" not in only:
                continue
            cut = frozenset(part.split("+")) - {"whole"}
            t0 = time.time()
            try:
                if side == "fwd":
                    jax.jit(functools.partial(fwd_call, cut=cut, **kw)).lower(
                        x, rows, rows, bc, bc).compile()
                else:
                    jax.jit(functools.partial(bwd_call, cut=cut, **kw)).lower(
                        x, x, rows, rows, bc, bc, states).compile()
                print(f"{side}:{part} accepted {time.time() - t0:.1f}s",
                      flush=True)
            except Exception as e:  # noqa: BLE001 - the refusal is the result
                print(f"{side}:{part} REFUSED {str(e)[-600:]}", flush=True)
    return 0


def timed(fn, args, calls):
    c = jax.jit(fn).lower(*args).compile()
    jax.block_until_ready(c(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = c(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / calls


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--only", default="")
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--describe", action="store_true",
                    help="compile every variant for a described v5e (no "
                    "chip, no time): what Mosaic refuses, it refuses here")
    args = ap.parse_args()
    global INTERPRET
    INTERPRET = jax.default_backend() != "tpu" and not args.describe
    if args.describe:
        return describe(args)
    b, t, h, p, n = (1, 512, 16, 64, 128) if args.tiny else \
        (2, 4096, 64, 64, 128)
    chunk = min(args.chunk, 128) if args.tiny else args.chunk
    hpb = 16
    x, dy, dt_t, cum_t, bm, cm = inputs(b, t, h, p, n, chunk)
    kw = dict(p=p, chunk=chunk, hpb=hpb)
    _, states = fwd_call(x, dt_t, cum_t, bm, cm, cut=frozenset(), **kw)
    only = set(filter(None, args.only.split(",")))
    res = {"device": jax.devices()[0].device_kind, "shape": [b, t, h, p, n],
           "chunk": chunk, "calls": args.calls, "ms": {}}
    for side, parts in PARTS.items():
        for part in parts:
            name = f"{side}:{part}"
            if only and name not in only:
                continue
            cut = frozenset(part.split("+")) - {"whole"}
            try:
                if side == "fwd":
                    ms = timed(functools.partial(fwd_call, cut=cut, **kw),
                               (x, dt_t, cum_t, bm, cm), args.calls)
                else:
                    ms = timed(functools.partial(bwd_call, cut=cut, **kw),
                               (x, dy, dt_t, cum_t, bm, cm, states),
                               args.calls)
                res["ms"][name] = round(ms, 4)
            except Exception as e:  # noqa: BLE001 - a refused variant
                res["ms"][name] = "refused: " + str(e)[-400:]
            print(name, res["ms"][name], file=sys.stderr, flush=True)
    print(json.dumps(res, indent=1))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ssd_ablate.json", "w") as f:
        json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
