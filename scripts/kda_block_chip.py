#!/usr/bin/env python3
"""KDA's kernel pair by the ORDER OF ISSUE inside a program, on the chip
(PR 66):

    python3 scripts/kda_block_chip.py [--tiny] [--other <tree>]
        [--forms staged,lockstep] [--headwise "<stage>,<stage>...[;...]"]
        [--without solve] [--ops N]

One KDA layer's ``kda_gated_scan`` (``ray_tpu/ops/kda_scan.py``, the model's
call: the norms and the gate made in the kernels) at
``kimilinear_train_s8192``'s shape: batch 2 x 8192 tokens, 32 heads of 128 x
128, bfloat16 q, k, v and step, four heads a program.

- ``--other <tree>``: that tree's ``ops/kda_scan.py`` (the parent's: a PAIR
  of heads a body, the pairs' solves one after the other) on the same
  inputs. o and the seven gradients of the two trees compared element by
  element (EQUAL, or the largest difference as a share of the other's
  largest entry), both held to the token-by-token recurrence on the first
  1024 tokens, and both timed: other, this, this, other.
- ``--forms``: this tree's body by how ``_staged`` issues the stages after
  the solve: ``staged`` (the file as it is: a stage for all the block's heads
  before the next) and ``lockstep`` (the pairs' solves in lock step ALONE:
  every later stage a head at a time). ``--headwise "a,b;c,d"`` adds the forms
  ``custom0``, ``custom1``: the named stages (keys of ``_staged``'s calls) a
  head at a time, the others for all the heads. The same products on the
  same numbers in every form: the outputs are compared with ``staged``'s.
- ``--without solve``: the timings again with the triangular solve taken out
  (T = I - A: WRONG numbers; the time that goes is the solve's), by a
  function that takes one matrix or a list of them in lock step
  (``benchmark/scratch/kda_kernel_chip.py --without solve`` hands a list to
  a function of one matrix since PR 66: a ``benchmark`` PR's to mend).
- every timing: the seconds the forward + backward took to trace and lower
  and to compile, the forward and the forward + backward host-timed over 5
  calls, and the device time of ``kda_chunk_fwd`` / ``kda_chunk_bwd`` a call
  from a traced run of 3; ``--ops N`` lists the largest device operations.

``--tiny`` walks it on the CPU (one row of 128 tokens, four heads). One JSON
object a line on stdout. A script, not a metric."""
import argparse
import importlib
import importlib.util
import json
import os
import sys
import tempfile
import time

NAMES = ("q", "k", "v", "step", "a_log", "dt_bias", "beta")


def load_other(tree: str):
    """``ops/kda_scan.py`` of another tree as a module of THIS tree's package
    (its relative imports find this tree's modules)."""
    spec = importlib.util.spec_from_file_location(
        "ray_tpu.ops.kda_scan_other",
        os.path.join(tree, "ray_tpu", "ops", "kda_scan.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def staged_but(headwise):
    """``_staged`` with the stages named in ``headwise`` (None: all of
    them) issued a head at a time: runs of such stages are walked head by
    head, the others a stage for all the heads."""
    def staged(heads, **stages):
        runs = []
        for name in stages:
            alone = headwise is None or name in headwise
            if not runs or runs[-1][0] != alone:
                runs.append((alone, []))
            runs[-1][1].append(name)
        for alone, names in runs:
            if alone:
                for f in heads:
                    for name in names:
                        f[name] = stages[name](f)
            else:
                for name in names:
                    for f in heads:
                        f[name] = stages[name](f)
    return staged


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--other", default="", help="a second tree's root")
    ap.add_argument("--forms", default="staged,lockstep")
    ap.add_argument("--headwise", default="")
    ap.add_argument("--without", default="", choices=["", "solve"])
    ap.add_argument("--ops", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import trace as T
    from benchmark.reference import kimi_linear as ref
    kda = importlib.import_module("ray_tpu.ops.kda_scan")
    other = load_other(args.other) if args.other else None

    b, t, h, d = (1, 128, 4, 128) if args.tiny else (2, 8192, 32, 128)
    r = jax.random.split(jax.random.PRNGKey(66), 8)
    shape = (b, t, h * d)
    bf = jnp.bfloat16
    q, k, v = (jax.nn.silu(jax.random.normal(r[i], shape)).astype(bf)
               for i in range(3))
    step = (0.5 * jax.random.normal(r[3], shape)).astype(bf)
    a_log = jnp.log(jax.random.uniform(r[4], (h,), minval=1.0, maxval=16.0))
    dt = jnp.exp(jax.random.uniform(r[5], (h * d,), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    beta = jax.nn.sigmoid(jax.random.normal(r[6], (b, t, h)))
    do = jax.random.normal(r[7], shape).astype(bf)
    scale = d ** -0.5
    inputs = (q, k, v, step, a_log, dt_bias, beta)
    say = lambda **kw: print(json.dumps(kw), flush=True)     # noqa: E731
    f32 = lambda x: x.astype(jnp.float32)                    # noqa: E731

    def call_of(mod):
        return lambda *x: mod.kda_gated_scan(*x, scale=scale)

    def with_grads(fn):
        def scalar(*x):
            o = fn(*x)
            return jnp.sum(f32(o) * f32(do)), o
        return jax.jit(jax.value_and_grad(
            scalar, argnums=tuple(range(7)), has_aux=True))

    def outputs(fn):
        (_, o), grads = with_grads(fn)(*inputs)
        return dict(zip(("o",) + NAMES, (o,) + grads))

    def timed(fn, n=5):
        jax.block_until_ready(fn(*inputs))
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*inputs)
        jax.block_until_ready(out)
        return round(1e3 * (time.perf_counter() - t0) / n, 3)

    def device_ops(fn, calls=3):
        """{operation: ms a call} of a traced run on the device."""
        jax.block_until_ready(fn(*inputs))
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            for _ in range(calls):
                out = fn(*inputs)
            jax.block_until_ready(out)
            jax.profiler.stop_trace()
            tr = T.load_xplane(T.find_xplane(tmp))
        if not tr.devices:
            return {}
        total = T.self_times(tr.devices[min(tr.devices)]["ops"])
        return {name: 1e3 * s / calls for name, s in total.items()}

    def times(label, mod):
        """A form's cost to build and to run, its bodies traced anew."""
        jax.clear_caches()
        fn = call_of(mod)
        t0 = time.perf_counter()
        lowered = with_grads(fn).lower(*inputs)
        t1 = time.perf_counter()
        lowered.compile()
        t2 = time.perf_counter()
        ops = device_ops(with_grads(fn))
        kernels = {name: round(sum(ms for op, ms in ops.items()
                                   if name in op), 3)
                   for name in (mod.KERNEL_NAMES["fwd"],
                                mod.KERNEL_NAMES["bwd"])}
        say(form=label, trace_lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2), fwd_ms=timed(jax.jit(fn)),
            fwd_bwd_ms=timed(with_grads(fn)), kernels_ms_a_call=kernels,
            all_ops_ms_a_call=round(sum(ops.values()), 3))
        return ops

    def compared(got, want):
        """{name: "EQUAL" or the largest difference over want's largest
        entry}, element by element."""
        return {n: "EQUAL" if bool((got[n] == want[n]).all()) else float(
            jnp.abs(f32(got[n]) - f32(want[n])).max()
            / (jnp.abs(f32(want[n])).max() + 1e-30)) for n in want}

    say(device=jax.devices()[0].device_kind, shape=[b, t, h, d],
        heads_a_program=kda._heads_per_block(h))
    as_it_is = kda._staged
    forms = {"staged": as_it_is, "lockstep": staged_but(None)}
    for i, names in enumerate(n for n in args.headwise.split(";") if n):
        forms[f"custom{i}"] = staged_but(set(names.split(",")))
    asked = [f for f in args.forms.split(",") if f] + [
        f for f in forms if f.startswith("custom")]

    # the numbers: this tree against the other, and against the recurrence
    mine = outputs(call_of(kda))
    say(this_tree="staged", finite=bool(all(
        jnp.all(jnp.isfinite(f32(x))) for x in mine.values())),
        routes=dict(kda.PATH_COUNTS))
    theirs = None
    if other is not None:
        theirs = outputs(call_of(other))
        say(this_tree_against=other.__file__, **compared(mine, theirs))
    for name in asked:
        if name == "staged":
            continue
        kda._staged = forms[name]
        jax.clear_caches()
        try:
            say(form=name, against="staged",
                **compared(outputs(call_of(kda)), mine))
        finally:
            kda._staged = as_it_is
    jax.clear_caches()
    n = min(t, 1024)
    cut = lambda x: x[:, :n]                                 # noqa: E731
    per_head = lambda x: f32(x).reshape(b, n, h, -1)         # noqa: E731
    g = -jnp.repeat(jnp.exp(a_log), d) * jax.nn.softplus(f32(step) + dt_bias)
    want = ref.delta_rule(
        ref.l2norm(per_head(cut(q))), ref.l2norm(per_head(cut(k))),
        per_head(cut(v)), per_head(cut(g)), cut(beta)).reshape(b, n, -1)
    for name, got in (("this", mine), ("other", theirs)):
        if got is not None:
            diff = jnp.abs(f32(cut(got["o"])) - want)
            say(tree=name, tokens_compared=n,
                max_abs_diff_to_recurrence=float(diff.max()),
                mean_abs_diff=float(diff.mean()),
                recurrence_abs_max=float(jnp.abs(want).max()))

    # the times: other, this tree's forms, the forms again, other
    def round_of(label, order):
        ops = {}
        for name in order:
            if name == "other":
                times(f"other {label}".strip(), other)
                continue
            kda._staged = forms[name]
            try:
                ops[name] = times(f"{name} {label}".strip(), kda)
            finally:
                kda._staged = as_it_is
        return ops

    first = (["other"] if other else []) + asked
    ops = round_of("", first + first[::-1])
    if args.ops and "staged" in ops:
        top = sorted(ops["staged"].items(), key=lambda kv: -kv[1])[:args.ops]
        say(form="staged", ops_ms_a_call={
            name: round(ms, 3) for name, ms in top},
            distinct_ops=len(ops["staged"]))

    def no_solve(a, r):       # one matrix, or a list of them in lock step
        if isinstance(a, (list, tuple)):
            return [no_solve(x, r) for x in a]
        return kda._same_block(a.shape, 1).astype(jnp.float32) - a

    if args.without:
        trees = [m for m in (kda, other) if m is not None]
        whole = [m._solve for m in trees]
        for m in trees:
            m._solve = no_solve
        try:
            round_of("without solve", first)
        finally:
            for m, fn in zip(trees, whole):
                m._solve = fn
    return 0


if __name__ == "__main__":
    sys.exit(main())
