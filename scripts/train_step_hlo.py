#!/usr/bin/env python3
"""Is the benchmark cell's train step the same program in two trees?

    python3 scripts/train_step_hlo.py --compare <tree A> <tree B>
    python3 scripts/train_step_hlo.py --tree <tree> --out <file>
    python3 scripts/train_step_hlo.py --tree <tree> --census [--cell <cell>]

Compiles ``bench_train_step`` of ``benchmark/lib/chip.py`` (the cell's
model, optimizer, batch and sequence; parameters and optimizer state
donated) for a DESCRIBED TPU v5e, as ``tests/test_chip_compile.py`` and
``benchmark/scratch/describe_compile.py`` do, and writes the optimised
HLO with source files and lines stripped: from the metadata, from the
module's tables of stack frames, and from the MLIR of the Pallas kernels
inside their custom calls (each kernel is written as the hash of its MLIR
printed without locations). ``--compare`` does so once per tree, each in
a process of its own that imports that tree's ``ray_tpu`` and
``benchmark``, and exits 0 when the two texts are the same, 1 with the
first differing lines when not. Nothing runs on a chip: this says the
programs are equal, never how fast they are.

``--census`` prints, for one tree, what the compiler made of the step
and not only whether it accepted it (PR 37): the bytes of its
temporaries, how many instructions the compiler's OWN rematerialisation
pass made again to fit the chip's memory (``.remat`` in their names:
work the model's remat policy never asked for), and the matmul
operations of the optimised program by the model's scope, a loop's body
counted by its trip count.
"""
from __future__ import annotations

import argparse
import base64
import difflib
import hashlib
import importlib
import json
import math
import os
import re
import subprocess
import sys
import tempfile

CELL = "gpt2m_train_s1024"
# where an instruction came from: attributes of its metadata, and the four
# tables at the head of the module that ``stack_frame_id`` indexes
_SOURCE = re.compile(r' (source_(file|line|end_line|column|end_column)'
                     r'|stack_frame_id)=("[^"]*"|\d+)')
_TABLES = re.compile(r'^(FileNames|FunctionNames|FileLocations|StackFrames)\n'
                     r'(\d+ .*\n)*\n?', re.M)
# a Pallas kernel rides in its custom call as base64 of MLIR bytecode,
# which carries the file and line of every operation as well
_KERNEL = re.compile(r'("custom_call_config":\{"body":")([^"]*)"')


def _kernel_without_locations(m: re.Match) -> str:
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True      # stable_mosaic
    with ctx:
        asm = ir.Module.parse(base64.b64decode(m.group(2))) \
            .operation.get_asm(enable_debug_info=False)
    return f'{m.group(1)}sha256 of the kernel\'s MLIR, locations off: ' \
        f'{hashlib.sha256(asm.encode()).hexdigest()}"'


def strip_locations(text: str) -> str:
    text = _SOURCE.sub("", _TABLES.sub("", text))
    return _KERNEL.sub(_kernel_without_locations, text)


def matmul_census(text: str) -> dict:
    """scope -> the matmul operations (2 M N K of every ``convolution``,
    which is what a ``dot_general`` is by then) of an optimised TPU
    program, a ``while`` body counted by the constant its condition
    compares with (a ``lax.scan`` counts from 0). The scope is the last
    ``jax.named_scope`` component of the instruction's ``op_name``."""
    comps, entry = {}, None
    for m in re.finditer(r"^(ENTRY )?(%\S+) \([^\n]*\{\n(.*?)^\}", text,
                         re.M | re.S):
        comps[m.group(2)] = m.group(3).splitlines()
        entry = m.group(2) if m.group(1) else entry
    elems = lambda shape: math.prod(  # noqa: E731
        int(n) for n in re.match(r"\w+\[([\d,]*)\]", shape).group(1)
        .split(",") if n)
    out: dict = {}

    def walk(comp: str, times: int) -> None:
        shapes = {}
        for line in comps.get(comp, ()):
            m = re.match(r"\s*(?:ROOT )?(%\S+) = (\w+\[[\d,]*\])?.*? "
                         r"([\w-]+)\((.*)$", line)
            if not m:
                continue
            inst, shape, op, rest = m.groups()
            shapes[inst] = shape
            if op == "convolution":
                a, b = (elems(shapes[o]) for o in
                        re.findall(r"%[\w.\-]+", rest)[:2])
                name = re.search(r'op_name="([^"]*)"', rest)
                scopes = re.findall(r"[/(]([a-z_]+)(?=[/)])",
                                    name.group(1) if name else "")
                scope = next((s for s in reversed(scopes) if s not in (
                    "jvp", "transpose", "while", "body", "closed_call",
                    "checkpoint", "rematted_computation", "dot_general")),
                    "unscoped")
                # [M, K] x [K, N] -> [M, N]: M N K = sqrt(MK * KN * MN)
                out[scope] = out.get(scope, 0) + times * 2 * int(
                    math.sqrt(a * b * elems(shape)))
            trip = 1
            if op == "while":
                cond = re.search(r"condition=(%[\w.\-]+)", rest).group(1)
                trip = int(re.search(r"s32\[\]\S* constant\((\d+)\)",
                                     "\n".join(comps[cond])).group(1))
            for callee in re.findall(r"(?:calls|body)=(%[\w.\-]+)", rest):
                walk(callee, times * trip)

    walk(entry, 1)
    return out


def compiler_remat(text: str) -> int:
    """How many instructions of an optimised program the compiler's own
    rematerialisation pass made again (it names them ``<name>.remat<n>``)."""
    return len(re.findall(r"^\s*(?:ROOT )?%\S*\.remat\S* = ", text, re.M))


def compile_step(cell: str, sharding):
    """``cell``'s train step compiled for the device of ``sharding`` from
    shapes alone; ``benchmark`` and ``ray_tpu`` are whichever ``sys.path``
    finds, the kernels whichever path ``kernel_common.use_interpret`` says."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import chip, spec

    c = spec.load_cell(cell)

    def shaped(tree_):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree_)

    model = spec.family_of(c).build(c["config_file"]["model"])
    tx = chip.make_optimizer(c["trainer"].get("optimizer", {}))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(tx.init, params)
    toks = jax.ShapeDtypeStruct(
        (int(c["trainer"]["batch"]), int(c["trainer"]["seq"])), jnp.int32,
        sharding=sharding)
    return jax.jit(chip.make_train_step(model, tx),
                   donate_argnums=(0, 1)).lower(
        shaped(params), shaped(opt), toks).compile()


def steer_kernels() -> None:
    """Every kernel file picks interpret mode by the one switch of
    ``ray_tpu.ops.kernel_common``, which reads ``jax.default_backend()``,
    the CPU here: take the compiled path, as the chip does."""
    importlib.import_module(
        "ray_tpu.ops.kernel_common").use_interpret = lambda: False


def dump(tree: str, out: str, cell: str, census: bool = False) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.abspath(tree))
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    steer_kernels()
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    compiled = compile_step(cell, SingleDeviceSharding(topo.devices[0]))
    text = compiled.as_text()
    if census:
        ops = matmul_census(text)
        print(json.dumps({
            "cell": cell,
            "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
            "compiler_remat_instructions": compiler_remat(text),
            "matmul_tera_ops": round(sum(ops.values()) / 1e12, 3),
            "by_scope": {k: round(v / 1e12, 3) for k, v in sorted(
                ops.items())}}))
        return
    with open(out, "w") as f:
        f.write(strip_locations(text))


def main() -> int:
    ap = argparse.ArgumentParser()
    what = ap.add_mutually_exclusive_group(required=True)
    what.add_argument("--compare", nargs=2, metavar="TREE")
    what.add_argument("--tree")
    ap.add_argument("--out", help="with --tree: where the text goes")
    ap.add_argument("--census", action="store_true",
                    help="with --tree: what the compiler made of the step")
    ap.add_argument("--cell", default=CELL)
    args = ap.parse_args()
    if args.tree:
        dump(args.tree, args.out, args.cell, args.census)
        return 0
    texts = []
    with tempfile.TemporaryDirectory() as tmp:
        for i, tree in enumerate(args.compare):
            out = os.path.join(tmp, f"{i}.hlo")
            subprocess.run([sys.executable, os.path.abspath(__file__),
                            "--tree", os.path.abspath(tree), "--out", out,
                            "--cell", args.cell],
                           check=True, cwd=tree)
            with open(out) as f:
                texts.append(f.read())
    for tree, text in zip(args.compare, texts):
        print(f"{hashlib.sha256(text.encode()).hexdigest()} "
              f"{len(text.splitlines())} lines  {tree}")
    if texts[0] == texts[1]:
        print("same optimised HLO")
        return 0
    diff = difflib.unified_diff(texts[0].splitlines(), texts[1].splitlines(),
                                *args.compare, lineterm="", n=0)
    for _, line in zip(range(40), diff):
        print(line[:300])
    return 1


if __name__ == "__main__":
    sys.exit(main())
