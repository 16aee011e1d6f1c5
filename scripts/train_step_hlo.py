#!/usr/bin/env python3
"""Is the benchmark cell's train step the same program in two trees?

    python3 scripts/train_step_hlo.py --compare <tree A> <tree B>
    python3 scripts/train_step_hlo.py --tree <tree> --out <file>

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
"""
from __future__ import annotations

import argparse
import base64
import difflib
import hashlib
import importlib
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


def dump(tree: str, out: str, cell: str) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.abspath(tree))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.lib import chip, spec

    # the kernels pick interpret mode from jax.default_backend(), the CPU
    # here: take the compiled path, as the chip does
    # (``ray_tpu.ops.flash_attention`` the attribute is the function)
    importlib.import_module(
        "ray_tpu.ops.flash_attention")._use_interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    c = spec.load_cell(cell)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(tree_):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree_)

    model = spec.family_of(c).build(c["config_file"]["model"])
    tx = chip.make_optimizer(c["trainer"].get("optimizer", {}))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(tx.init, params)
    toks = jax.ShapeDtypeStruct(
        (int(c["trainer"]["batch"]), int(c["trainer"]["seq"])), jnp.int32,
        sharding=one)
    text = jax.jit(chip.make_train_step(model, tx),
                   donate_argnums=(0, 1)).lower(
        shaped(params), shaped(opt), toks).compile().as_text()
    with open(out, "w") as f:
        f.write(strip_locations(text))


def main() -> int:
    ap = argparse.ArgumentParser()
    what = ap.add_mutually_exclusive_group(required=True)
    what.add_argument("--compare", nargs=2, metavar="TREE")
    what.add_argument("--tree")
    ap.add_argument("--out", help="with --tree: where the text goes")
    ap.add_argument("--cell", default=CELL)
    args = ap.parse_args()
    if args.tree:
        dump(args.tree, args.out, args.cell)
        return 0
    texts = []
    with tempfile.TemporaryDirectory() as tmp:
        for i, tree in enumerate(args.compare):
            out = os.path.join(tmp, f"{i}.hlo")
            subprocess.run([sys.executable, os.path.abspath(__file__),
                            "--tree", tree, "--out", out,
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
