"""The main path's programs compile for the chip: a DESCRIBED TPU v5e, not an
attached one (on-chip-measurement guide, section 2). Nothing runs, so these
say nothing about results or speed; they catch what the chip's compiler
refuses (tiling, VMEM, HBM) before a chip run has to.

The topology is described only inside the module-scoped fixture ``one_chip``
(``tests/_chip_compile.py``, shared with ``test_cell_step_compile.py``, where
a cell's WHOLE train step is compiled, marked ``slow``), so importing this
file loads nothing, and only the xdist worker that runs it
takes libtpu. The shapes are chip_smoke.py's: GPT-2 small at full width,
S=1024, the train batch chosen there and the serve engine's programs.
"""
import ast
import importlib
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from _chip_compile import (compiled_kernels, fits, hlo_tool,  # noqa: F401
                           one_chip)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def test_flash_attention_fwd_bwd_gpt2_small(one_chip, compiled_kernels):
    from ray_tpu.ops.flash_attention import flash_attention

    qkv = jax.ShapeDtypeStruct((8, 1024, 12, 64), jnp.bfloat16,
                               sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv).compile().as_text()
    # forward kernel + the fused single-block backward kernel
    assert text.count("tpu_custom_call") >= 2
    assert _kernel_names(text) == {"flash_fwd_single", "flash_bwd_fused"}


@pytest.mark.parametrize("b,s,h,d", [(8, 1024, 16, 64), (2, 1024, 8, 128)],
                         ids=["gpt2_medium", "hd128"])
def test_flash_attention_merged_layout(one_chip, compiled_kernels,
                                       b, s, h, d):
    """The benchmark cell's attention (B=8, S=1024, 16 heads of 64), and a
    head of 128 to a column block (B=2, S=1024, 8 heads), handed over as
    models/gpt.py hands it: [B, S, H*hd] arrays reshaped to four
    dimensions and back. The kernels index the merged arrays, so the
    compiled program holds the two single-block kernels, each working its
    four causal row bands of 256 inside one program, and no array whose
    minor dimension is a head of 64 (it would pad to 128 lanes in HBM and
    be a copy of 16 MB for each of q, k, v, o, dO, dq, dk, dv)."""
    import re

    from ray_tpu.ops.flash_attention import (BAND_COUNTS, PATH_COUNTS,
                                             flash_attention)

    merged = jax.ShapeDtypeStruct((b, s, h * d), jnp.bfloat16,
                                  sharding=one_chip)

    def loss(q, k, v):
        heads = lambda x: x.reshape(b, s, h, d)  # noqa: E731
        out = flash_attention(heads(q), heads(k), heads(v), causal=True)
        return out.reshape(b, s, h * d).astype(jnp.float32).sum()

    before = PATH_COUNTS["merged"], BAND_COUNTS[4]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        merged, merged, merged).compile().as_text()
    assert (PATH_COUNTS["merged"], BAND_COUNTS[4]) == (before[0] + 1,
                                                       before[1] + 1)
    assert _kernel_names(text) == {"flash_fwd_single", "flash_bwd_fused"}
    assert not re.findall(r"\w+\[[\d,]*,64\]", text)
    assert " transpose(" not in text and " copy(" not in text


def _kernel_names(compiled_text: str, latent: bool = False) -> set:
    """The Pallas kernels of a compiled program by the names a device
    trace shows: each is a custom call to ``tpu_custom_call`` whose
    instruction is named after the kernel (``%flash_bwd_fused.9`` in the
    train step, ``%transpose_jvp_flash_bwd_dq__.1`` under a bare grad).
    ``latent``: among the latent route's names."""
    from ray_tpu.ops.flash_attention import KERNEL_NAMES, LATENT_KERNEL_NAMES

    names = LATENT_KERNEL_NAMES if latent else KERNEL_NAMES
    longest_first = sorted(names.values(), key=len, reverse=True)
    out = set()
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            inst = line.split(" = ")[0]
            out.add(next((n for n in longest_first if n in inst), inst))
    return out


def test_flash_attention_streamed_kernels_are_named(one_chip,
                                                    compiled_kernels):
    """S=1024 in blocks of 512: the streamed forward and the two-pass
    backward, the three kernels the single-block case does not reach."""
    from ray_tpu.ops.flash_attention import flash_attention

    qkv = jax.ShapeDtypeStruct((2, 1024, 4, 64), jnp.bfloat16,
                               sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=512,
                               block_k=512).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv).compile().as_text()
    assert _kernel_names(text) == {"flash_fwd", "flash_bwd_dq",
                                   "flash_bwd_dkv"}


@pytest.mark.parametrize("s,sm_scale", [(8192, None), (4096, 0.1447)],
                         ids=["kanana2_train_s8192", "xing4_train_s4096"])
def test_latent_attention_at_the_benchmark_cells_shape(one_chip,
                                                       compiled_kernels, s,
                                                       sm_scale):
    """ISSUE 33: kanana2_train_s8192's attention, B=2, S=8192, 32 heads of
    128 + 64 against 128, one shared rope key, at the default blocks of
    1024, fed as the model feeds them, with no copy of a head-shaped array
    around them. ISSUE 34: the forward and ONE backward kernel, under the
    dk/dv kernel's name; it keeps dq of a head block for the whole
    sequence in VMEM, 46 MB with the tiles, which only the chip's
    compiler checks. ISSUE 45: xing4_train_s4096's, the same heads at
    S=4096 with YaRN's softmax scale (2.0047 / sqrt(192)) handed in.
    ISSUE 46: both kernels work the blocks the diagonal crosses in four
    row bands of 256 (static slices of refs and of the scratch, two of
    them views at a dynamic block), which only the chip's compiler
    checks."""
    import re

    from ray_tpu.ops.flash_attention import BAND_COUNTS, flash_attention

    b, h = 2, 32
    sd = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.bfloat16, sharding=one_chip)

    def loss(qn, kn, v, qr, kr):
        heads = lambda x: x.reshape(b, s, h, -1)  # noqa: E731
        return flash_attention(heads(qn), heads(kn), heads(v), causal=True,
                               q_rope=heads(qr), k_rope=kr,
                               sm_scale=sm_scale).astype(
            jnp.float32).sum()

    before = BAND_COUNTS[4]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        sd(b, s, h * 128), sd(b, s, h * 128), sd(b, s, h * 128),
        sd(b, s, h * 64), sd(b, s, 64)).compile().as_text()
    assert BAND_COUNTS[4] == before + 1
    assert _kernel_names(text, latent=True) == {"flash_latent_fwd",
                                                "flash_latent_bwd_dkv"}
    # q_rope, two heads of 64 to a block, is never laid out by head
    assert not re.findall(r"\w+\[2,%d,32,64\]" % s, text)
    assert " transpose(" not in text


@pytest.mark.parametrize(
    "t,d,f,held,of,shared,top_k,scale,expert,latent",
    [(16384, 2048, 768, 16, 128, 2, 6, 2.448, "swiglu", 0),
     (8192, 3584, 1024, 8, 64, 1, 4, 2.0, "swiglu", 0),
     (16384, 4096, 2688, 8, 512, 2, 22, 5.0, "relu2", 1024),
     (16384, 2048, 512, 32, 512, 1, 10, 1.0, "swiglu", 0),
     (16384, 2048, 768, 16, 128, 0, 8, 1.0, "swiglu", 0),
     (16384, 2048, 1792, 8, 32, 0, 4, 1.0, "swiglu", 0),
     (16384, 2304, 1024, 8, 256, 1, 8, 2.446, "swiglu", 0)],
    ids=["kanana2_train_s8192", "xing4_train_s4096",
         "nemotron3super_train_s8192", "qwen3next_train_s8192",
         "keyevl2_train_s16384", "lfm2moe_train_s8192",
         "kimilinear_train_s8192"])
def test_held_expert_layer_at_the_benchmark_cells_shape(
        one_chip, compiled_kernels, t, d, f, held, of, shared, top_k, scale,
        expert, latent):
    """ISSUE 33: 16 384 tokens, 16 of 128 experts of 2048 x 768 held, top
    6: forward and backward of the layer, the two grouped-product kernels
    by name, no scatter. ISSUE 45: 8192 tokens, 8 of 64 experts of
    3584 x 1024 held, top 4, one shared expert. ISSUE 57: 16 384 tokens of
    4096, 8 of 512 squared-ReLU experts of 1024 x 2688 in a latent of 1024,
    a shared expert of 5376, top 22: a token holds at most 8 rows, so the
    pair domain is 131 072 and no array of the program is sized by the
    360 448 (token, choice) pairs. ISSUE 62: at all three, no select with a
    [rows, width] result: the buffer's padding rows are not written as
    zeros. ISSUE 65: 16 384 tokens, 32 of 512 experts of 2048 x 512, top
    10, and at all four: the rows gathered back are summed as a BITCAST of
    the gather's [slots * T, width], to [slots, T, width] where the slots
    are not whole tiles of 8 rows (6, 4 and 10: in the second-minor
    dimension they were one padded copy a gather back) and to [T, slots,
    width] where they are (nemotron3super's 8, the program it had), and no
    ``reshape``, ``copy`` or ``transpose`` outside a fusion makes or reads
    an array of that size. ISSUE 68: at all seven router cells' shapes
    (keyevl2's 16 of 128 of 2048 x 768, top 8, and lfm2moe's 8 of 32 of
    2048 x 1792, top 4, neither with a shared expert; kimilinear's 8 of 256
    of 2304 x 1024, top 8) the experts' first half is the kernel pair
    ``expert_hidden_fwd`` / ``expert_hidden_bwd``, one call of each, with
    all of F a block (``hidden_block``: lfm2moe's two 2048 x 1792 matrices
    and xing4's two of 3584 x 1024 beside their second buffers are what the
    64 MB of fast memory must take), and outside the kernels nothing elementwise is left that is
    sized [buffer rows, F]: no fusion, convert, select or add makes such an
    array (the unfused first half made eight to twelve a layer)."""
    el = importlib.import_module("ray_tpu.ops.expert_layer")
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    width = latent or d
    p = {"w_router": sd((d, of)), "router_bias": sd((of,)),
         "e_up": sd((held, width, f)), "e_down": sd((held, f, width))}
    if shared:
        p.update(s_up=sd((d, shared * f)), s_down=sd((shared * f, d)))
    if expert == "swiglu":
        p["e_gate"] = sd((held, width, f))
        if shared:
            p["s_gate"] = sd((d, shared * f))
    if latent:
        p.update(w_fc1=sd((d, latent)), w_fc2=sd((latent, d)))

    def loss(x, p):
        return el.held_expert_layer(
            x, p, experts_held=held, expert_offset=0, top_k=top_k,
            routed_scale=scale, expert=expert)[0].astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        sd((t, d), jnp.bfloat16), p).compile().as_text()
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert any("grouped_matmul_dw" in c for c in calls)
    assert any("grouped_matmul" in c and "_dw" not in c for c in calls)
    for name in ("expert_hidden_fwd", "expert_hidden_bwd"):
        assert sum(1 for c in calls if name in c) == 1, (name, calls)
    assert " scatter(" not in text
    if top_k > held:
        assert re.search(r"\[%d[,\]]" % (t * held), text)
        assert not re.search(r"\[%d[,\]]" % (t * top_k), text)
    # ISSUE 62: a padding row's zero is its weight. No select writes zeros
    # over the buffer (PR 61's text holds two, ``select(filled, gathered,
    # 0)`` forward and backward; a step that makes the forward again three):
    # the scalar one a row and the one over the pair domain are all
    rows = el.buffer_rows(t, top_k, held)
    selects = lambda shape: re.findall(  # noqa: E731
        r"= \w+\[%s\]\S* select\(" % shape, text)
    assert not selects("%d,%d" % (rows, width))
    # (ISSUE 68: one scalar a row still, a column now: the kernel pair
    # takes the rows' weights [rows, 1])
    assert selects("%d(?:,1)?" % rows)
    # ISSUE 68: what is [rows, F] is made by a kernel (h, the cotangents of
    # h and of the pre-activations) and by nothing else
    wide = re.findall(r"= \w+\[%d,%d\]\S* ([\w-]+)\(" % (rows, f), text)
    assert wide and set(wide) <= {"custom-call", "get-tuple-element",
                                  "bitcast", "parameter"}, wide
    slots = min(top_k, held)
    summed = (t, slots, width) if el.slot_axis(slots) else (slots, t, width)
    assert selects("%d,%d,%d" % summed)
    # ISSUE 65: between the gather back and the sum over a token's slots
    # nothing moves the rows. An instruction of the ENTRY computation (a
    # fusion's own lines carry no ``backend_config``) over one of the
    # gathered array's three shapes is a bitcast or a fusion, never a copy
    gathered = r"\[(%d,%d,%d|%d,%d,%d|%d,%d)\]" % (
        slots, t, width, t, slots, width, slots * t, width)
    moves = re.findall(r"= bf16%s\S* (reshape|copy|transpose)\([^\n]*"
                       r"backend_config" % gathered, text)
    assert not moves, moves
    assert re.search(r"= bf16\[%d,%d,%d\]\S* bitcast\(" % summed, text)


def test_hyper_connection_pair_at_the_benchmark_cells_shape(
        one_chip, compiled_kernels):
    """ISSUE 47: forward + backward of ONE sublayer's mixings at
    xing4_train_s4096's shape, 4 streams [2, 4096, 3584] in bfloat16, the
    sublayer between them a doubling: the kernel route, its four kernels
    by name, and nothing of a stream's size turned: no transpose at all,
    and the only copies are of Φ and of the coefficients' parameters
    (autodiff's 24 reductions over d used to turn all eight streams
    tokens-minor, sixteen 117 MB copies a sublayer)."""
    hc = importlib.import_module("ray_tpu.ops.hyper_connection")
    n, d = 4, 3584
    x = tuple(jax.ShapeDtypeStruct((2, 4096, d), jnp.bfloat16,
                                   sharding=one_chip) for _ in range(n))
    p = {k: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
         for k, shape in hc.hc_param_shapes(n, d).items()}
    before = hc.ROUTE_COUNTS["kernel"], hc.ROUTE_COUNTS["plain"]

    def loss(x, p):
        out, _ = hc.hc_mix(x, p, lambda z: (2 * z, None), iters=20, eps=1e-6,
                           clamp=(-30.0, 30.0), rms_eps=1e-6)
        return sum(jnp.sum(jnp.square(o.astype(jnp.float32))) for o in out)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        x, p).compile().as_text()
    assert (hc.ROUTE_COUNTS["kernel"], hc.ROUTE_COUNTS["plain"]) \
        == (before[0] + 1, before[1])
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in hc.KERNEL_NAMES.values():
        assert sum(1 for c in calls
                   if re.match(r"\s*%?" + name + r"(\.\d+)?$", c)) == 1, name
    assert " transpose(" not in text
    moved = [line for line in text.splitlines()
             if re.search(r" (copy|transpose)\(", line)
             and re.search(r"\[(2,4096|8192|3584,2,4096|3584,8192)[,\]]",
                           line.split(" = ")[1].split(" ")[0])]
    assert not moved, moved[:2]


def test_ssd_scan_at_the_benchmark_cells_shape(one_chip, compiled_kernels):
    """ISSUE 36: granite4h_train_s4096's state-space scan, B=2, S=4096, 64
    heads of 64 with a state of 128, one group, chunks of 256, fed as the
    model feeds it ([B, S, H*P] reshaped): forward and backward are the
    two kernels by name, and NO array with two chunk-long dimensions (a
    [.., chunks, heads, 256, 256] decay or score matrix: 268 MB a layer in
    float32) is anywhere in the compiled program: they live in VMEM."""
    import re

    ssd = importlib.import_module("ray_tpu.ops.ssd_scan")
    b, t, h, p, n, chunk = 2, 4096, 64, 64, 128, 256
    sd = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)

    def loss(x, dt, a, bm, cm, d):
        return ssd.ssd_scan(x.reshape(b, t, h, p), dt, a, bm[:, :, None],
                            cm[:, :, None], d, chunk=chunk).astype(
            jnp.float32).sum()

    before = ssd.PATH_COUNTS["kernel"]
    text = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        sd((b, t, h * p)), sd((b, t, h), jnp.float32), sd((h,), jnp.float32),
        sd((b, t, n)), sd((b, t, n)), sd((h,), jnp.float32)
    ).compile().as_text()
    assert ssd.PATH_COUNTS["kernel"] == before + 1
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    assert any(ssd.KERNEL_NAMES["fwd"] in c for c in calls)
    assert any(ssd.KERNEL_NAMES["bwd"] in c for c in calls)
    assert not re.findall(r"\w+\[[\d,]*%d,%d[\d,]*\]" % (chunk, chunk), text)
    # nor is x laid out by head: two heads of 64 share a 128-lane tile
    assert not re.findall(r"\w+\[2,4096,64,64\]", text)


@pytest.mark.parametrize("shape,bias", [
    ((2, 8192, 4096), False), ((2, 8192, 8192), False),
    ((2, 4096, 4352), True)], ids=["kimilinear", "qwen3next", "granite4h"])
def test_conv_silu_backward_is_two_fusions_and_one_array(one_chip, shape,
                                                         bias):
    """ISSUE 55: value and gradient of ``causal_conv1d_silu`` at the three
    cells' shapes. The backward is at most TWO fusions whose result holds a
    full-size array (dpre with the sums for dw and db; dx) and its
    temporaries at most one such array (dpre). Autodiff's of the same
    expression is three such fusions (dpre; four full-size shifted
    products, one a tap; their padded sum) and four arrays."""
    from ray_tpu.ops.layers import causal_conv1d, causal_conv1d_silu

    full = "bf16[%d,%d,%d]" % shape
    array_bytes = 2 * math.prod(shape)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    params = (jax.ShapeDtypeStruct((4, shape[2]), jnp.float32,
                                   sharding=one_chip),)
    if bias:
        params += (jax.ShapeDtypeStruct((shape[2],), jnp.float32,
                                        sharding=one_chip),)

    def backward_of(f):
        def value_and_grads(dy, x, *params):
            y, vjp = jax.vjp(f, x, *params)
            return y, vjp(dy)
        compiled = jax.jit(value_and_grads).lower(x, x, *params).compile()
        entry = compiled.as_text().split("\nENTRY ")[1]
        writers = [line for line in entry.splitlines() if " fusion(" in line
                   and full in line.split(" fusion(")[0].partition(" = ")[2]]
        # less the forward's one fusion, x -> y
        return (len(writers) - 1,
                compiled.memory_analysis().temp_size_in_bytes)

    fusions, temp = backward_of(causal_conv1d_silu)
    assert fusions <= 2 and temp <= 1.01 * array_bytes
    fusions, temp = backward_of(
        lambda x, *p: jax.nn.silu(causal_conv1d(x, *p)))
    assert fusions == 3 and temp >= 3.9 * array_bytes


def test_gated_short_conv_at_the_benchmark_cells_shape(one_chip,
                                                       compiled_kernels):
    """ISSUE 64: lfm2moe_train_s8192's conv operator (norm, W_in, the gated
    3-tap convolution, W_out) at the cell's batch, 2 rows of 8192 at width
    2048, forward and backward, for the described v5e: the two kernels by
    name, once each (a bare grad keeps nothing, so the forward's output is
    dead and its kernel with it: the backward makes z and the taps again
    from b, c, x), blocks with a halo of 16 rows which only the chip's
    compiler checks. The kernels' operand is W_in's ONE output and the
    backward's result the ONE cotangent W_in's backward reads: no chunk
    [2, 8192, 2048] is copied out or padded back under the scope ``conv``,
    nothing stands there but the two calls, and the taps' gradient is a
    [3, 2048] float32 output of the backward call."""
    from ray_tpu.models import Lfm2Moe, Lfm2MoeConfig

    sc = importlib.import_module("ray_tpu.ops.short_conv")
    model = Lfm2Moe(Lfm2MoeConfig.lfm2_8b_a1b(
        layer_types=("conv",), num_dense_layers=1, experts_held=8,
        vocab_size=16384, max_seq=8192))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    lp = {n.split(".", 2)[2]: jax.ShapeDtypeStruct(
        v.shape[1:], v.dtype, sharding=one_chip)
        for n, v in shapes.items() if n.startswith("0.")
        and n.split(".")[-1] in ("norm", "w_in", "conv_w", "w_out")}
    x = jax.ShapeDtypeStruct((2, 8192, 2048), jnp.bfloat16,
                             sharding=one_chip)

    def loss(x, lp):
        return model._conv_operator(x, lp).astype(jnp.float32).sum()

    before = sc.PATH_COUNTS["kernel"]
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        x, lp).compile().as_text()
    assert sc.PATH_COUNTS["kernel"] == before + 1
    calls = [line.split(" = ")[0].strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(re.sub(r"^%|\.\d+$", "", c) for c in calls) == \
        ["short_conv_bwd", "short_conv_fwd"], calls
    bwd = [line for line in text.splitlines()
           if re.match(r"\s*%?short_conv_bwd", line)][0]
    assert "f32[3,2048]" in bwd.split(" custom-call(")[0]
    assert "bf16[2,8192,6144]" in bwd.split(" custom-call(")[0]
    under_conv = [line.split(" = ")[0].strip() for line in text.splitlines()
                  if re.search(r'op_name="[^"]*[/(]conv[/)]', line)
                  and re.search(r" = \(?\w+\[2,8192,", line)
                  and " get-tuple-element(" not in line]
    assert sorted(re.sub(r"^%|\.\d+$", "", c) for c in under_conv) == \
        ["short_conv_bwd", "short_conv_fwd"], under_conv


def test_selective_scan_at_the_benchmark_cells_shape(one_chip,
                                                     compiled_kernels):
    """ISSUE 43: phi4flash_train_s8192's Mamba-1 scan, B=1, S=8192, 5120
    channels with a state of 16, chunks of 128, as the model feeds it:
    forward and backward are the two kernels by name, and NO array with
    the sequence, the channels AND the state (the decays or states of a
    row: 2.7 GB in float32) is anywhere in the compiled program: the state
    lives in VMEM, and what reaches HBM of it is one [16, 5120] a chunk."""
    import re

    sel = importlib.import_module("ray_tpu.ops.selective_scan")
    b, t, c, n = 1, 8192, 5120, 16
    sd = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)

    def loss(x, dt, a, bm, cm, d):
        return sel.selective_scan(x, dt, a, bm, cm, d, chunk=128).astype(
            jnp.float32).sum()

    before = sel.PATH_COUNTS["kernel"]
    text = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        sd((b, t, c)), sd((b, t, c), jnp.float32), sd((c, n), jnp.float32),
        sd((b, t, n)), sd((b, t, n)), sd((c,), jnp.float32)
    ).compile().as_text()
    assert sel.PATH_COUNTS["kernel"] == before + 1
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    assert any(sel.KERNEL_NAMES["fwd"] in c for c in calls)
    assert any(sel.KERNEL_NAMES["bwd"] in c for c in calls)
    assert not re.findall(r"f32\[1,8192,(16,5120|5120,16)\]", text)
    assert re.findall(r"f32\[1,64,16,5120\]", text)     # the chunks' states


def test_windowed_flash_at_the_benchmark_cells_shape(one_chip,
                                                     compiled_kernels):
    """ISSUE 43: phi4flash_train_s8192's window layer as the model calls
    it: 80 heads of 64 (four a differential head), S=8192, window 512,
    blocks of 1024: the three streamed kernels by name, within the scoped
    VMEM a windowed call asks for (``_window_vmem``; at the default 16 MB
    the dk/dv kernel is refused by 0.6 MB)."""
    from ray_tpu.ops.flash_attention import flash_attention

    qkv = jax.ShapeDtypeStruct((1, 8192, 80, 64), jnp.bfloat16,
                               sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, sm_scale=0.125,
                               window=512).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv).compile().as_text()
    assert _kernel_names(text) == {"flash_fwd", "flash_bwd_dq",
                                   "flash_bwd_dkv"}


@pytest.mark.parametrize("window", [None, 512], ids=["full", "window-512"])
def test_paired_flash_at_the_benchmark_cells_shape(one_chip, compiled_kernels,
                                                   window):
    """ISSUE 44: phi4flash_train_s8192's attention as the model calls it
    now: 40 score heads of 64 in pairs against 20 values of 128, S=8192,
    blocks of 1024, full causal (the full and the cross layer) and under
    the window of 512: the three streamed kernels by name and no other
    custom call, within the scoped VMEM each call asks for (a paired
    program's dO and o tiles are [1024, 256]; the full call fits the
    compiler's default, the windowed one asks what a windowed call
    asks)."""
    from ray_tpu.ops.flash_attention import flash_attention

    qk = jax.ShapeDtypeStruct((1, 8192, 40, 64), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 8192, 20, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, sm_scale=0.125,
                               window=window).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qk, qk, v).compile().as_text()
    assert _kernel_names(text) == {"flash_fwd", "flash_bwd_dq",
                                   "flash_bwd_dkv"}
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    # nothing is expanded to 80 heads around the kernels
    assert "bf16[1,8192,5120]" in text and "[1,8192,80," not in text


@pytest.mark.parametrize("entry", ["scan", "gated"])
def test_kda_scan_at_the_benchmark_cells_shape(one_chip, compiled_kernels,
                                               entry):
    """ISSUE 50: kimilinear_train_s8192's delta-rule scan, B=2, S=8192, 32
    heads of 128 x 128, chunks of 64, fed as the model feeds it (merged
    [B, S, H*128], g float32): the call takes the kernel route, forward and
    backward are ONE ``kda_chunk_fwd`` and ONE ``kda_chunk_bwd`` and both
    compile for the chip; no array of a chunk's score matrices ([.., 64,
    64]), of a sub-block's pairwise differences ([.., 8, 8, 128]) or of its
    solve reaches HBM: of [128 chunks, ...] there is nothing but the states
    the chunks start from, [2, 128, 4096, 128] float32, and beta's
    head-major rows. ISSUE 51, ``gated``: the model's call since, q, k and
    the gate projection's step in bf16 as their layers left them, A_log and
    dt_bias: the same two kernels make the norms and the gate, and NO
    float32 [2, 8192, 4096] array (g, dg, a norm's square) is anywhere in
    the program; the rows' gradients leave the kernel as [2, 2, 4096]
    partial sums."""
    kda = importlib.import_module("ray_tpu.ops.kda_scan")
    b, t, h, d = 2, 8192, 32, 128
    sd = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    wide = sd((b, t, h * d))
    if entry == "gated":
        fn = kda.kda_gated_scan
        args = (wide, wide, wide, wide, sd((h,), jnp.float32),
                sd((h * d,), jnp.float32), sd((b, t, h), jnp.float32))
    else:
        fn = kda.kda_scan
        args = (wide, wide, wide, sd((b, t, h * d), jnp.float32),
                sd((b, t, h), jnp.float32))

    def loss(*a):
        return fn(*a, scale=d ** -0.5).astype(jnp.float32).sum()

    before = kda.PATH_COUNTS.copy()
    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(len(args))))
                       ).lower(*args).compile()
    assert kda.PATH_COUNTS - before == {"kernel": 1}
    text = compiled.as_text()
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    for part in ("fwd", "bwd"):
        name = kda.KERNEL_NAMES[part]
        assert sum(name in c for c in calls) == 1, (name, calls)
    # whatever is a chunk's own stays in VMEM: no score matrices, no
    # pairwise differences, and every array of a million elements or more
    # is an input, the output, a gradient or the chunk-start states
    assert not re.findall(r"\w+\[[\d,]*64,64\]", text)
    assert not re.findall(r"\w+\[[\d,]*8,8,128\]", text)
    large = {shape for shape in re.findall(r"\w+\[([\d,]+)\]", text)
             if math.prod(int(n) for n in shape.split(",")) >= 1 << 20}
    assert large == {"2,8192,4096", "2,128,4096,128"}, large
    assert "f32[2,128,4096,128]" in text
    if entry == "gated":
        assert "f32[2,8192,4096]" not in text
        assert "f32[2,2,4096]" in text
    # 4 units of [2, 8192, 4096] bf16 in, their gradients out, and the
    # chunk-start states (537 MB): well under the 5 GB the step can spare
    # (read: 0.67 GB with the gate made in the kernels)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        3.0e9 if entry == "scan" else 0.8e9)


def test_gdn_gated_scan_at_the_benchmark_cells_shape(one_chip,
                                                     compiled_kernels):
    """ISSUE 53: qwen3next_train_s8192's Gated DeltaNet scan, B=2, S=8192,
    32 value heads over 16 key heads of 128 x 128, fed as the model feeds
    it (q and k merged [B, S, 16 x 128], v [B, S, 32 x 128], ``a`` bf16 and
    beta float32 [B, S, 32]): the call takes the kernel route through the
    body for one decay a head, forward and backward are ONE
    ``gdn_chunk_fwd`` and ONE ``gdn_chunk_bwd`` (KDA's pair is not in the
    program) and both compile for the chip. q and k are NOT repeated to the
    value heads and ``a`` is not spread over a head's lanes: the only
    arrays of a million elements are q, k and their gradients at 2048
    columns, v, o and their gradients at 4096 and the chunk-start states;
    nothing float32 of [2, 8192, 4096] (a g, a spread ``a``, a norm's
    square) and no score matrix or decay table ([.., 64, 64]) reaches
    HBM; A_log's and dt_bias's gradients leave the kernel as partial sums
    a batch row, head block and token of the chunk, [2, 8, 16, 64]."""
    kda = importlib.import_module("ray_tpu.ops.kda_scan")
    b, t, hk, hv, d = 2, 8192, 16, 32, 128
    sd = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    args = (sd((b, t, hk * d)), sd((b, t, hk * d)), sd((b, t, hv * d)),
            sd((b, t, hv)), sd((hv,), jnp.float32), sd((hv,), jnp.float32),
            sd((b, t, hv), jnp.float32))

    def loss(*a):
        return kda.gdn_gated_scan(*a, scale=d ** -0.5).astype(
            jnp.float32).sum()

    before = kda.PATH_COUNTS.copy()
    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(7)))
                       ).lower(*args).compile()
    assert kda.PATH_COUNTS - before == {"kernel": 1}
    text = compiled.as_text()
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    for part in ("gdn_fwd", "gdn_bwd"):
        name = kda.KERNEL_NAMES[part]
        assert sum(name in c for c in calls) == 1, (name, calls)
    assert not re.findall(r"\w+\[[\d,]*64,64\]", text)
    large = {shape for shape in re.findall(r"\w+\[([\d,]+)\]", text)
             if math.prod(int(n) for n in shape.split(",")) >= 1 << 20}
    assert large == {"2,8192,4096", "2,8192,2048", "2,128,4096,128"}, large
    assert "f32[2,128,4096,128]" in text
    assert "f32[2,8192,4096]" not in text
    assert "f32[2,8,16,64]" in text
    # v, do in and o, dv out at 4096 columns, q, k and dq, dk at 2048, the
    # chunk-start states (537 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.8e9


def test_gdn_gated_scan_on_padded_lanes_at_the_benchmark_cells_shape(
        one_chip, compiled_kernels):
    """ISSUE 67: olmohybrid_train_s8192's Gated DeltaNet scan, B=1, S=8192,
    15 heads of 96 key and 192 value channels, fed as the model feeds it (q
    and k merged [B, S, 15 x 96], v [B, S, 15 x 192], ``a`` bf16 and beta
    float32 [B, S, 15], the key heads stated, ``beta_max`` 2): the call
    takes the KERNEL route on padded lanes (a head's keys on one 128-lane
    tile, its values on two; the solve in blocks of 4), forward and
    backward are ONE ``gdn_chunk_fwd`` and ONE ``gdn_chunk_bwd`` and both
    compile for the chip. The kernels read q and k at 15 x 128 columns and
    v at 30 x 128; what the padding adds to HBM is those copies, their
    gradients' and the padded o, no float32 array of the batch, no score
    matrix and no decay table."""
    kda = importlib.import_module("ray_tpu.ops.kda_scan")
    b, t, h, dk, dv = 1, 8192, 15, 96, 192
    sd = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    args = (sd((b, t, h * dk)), sd((b, t, h * dk)), sd((b, t, h * dv)),
            sd((b, t, h)), sd((h,), jnp.float32), sd((h,), jnp.float32),
            sd((b, t, h), jnp.float32))

    def loss(*a):
        return kda.gdn_gated_scan(*a, scale=dk ** -0.5, key_heads=h,
                                  beta_max=2.0).astype(jnp.float32).sum()

    before = kda.PATH_COUNTS.copy()
    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(7)))
                       ).lower(*args).compile()
    assert kda.PATH_COUNTS - before == {"kernel": 1}
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    for part in ("gdn_fwd", "gdn_bwd"):
        name = kda.KERNEL_NAMES[part]
        found = [c for c in calls if name in c.split(" = ")[0]]
        assert len(found) == 1, (name, calls)
        assert "bf16[1,8192,1920]" in found[0] and "bf16[1,8192,3840]" in \
            found[0]
    assert not re.findall(r"\w+\[[\d,]*64,64\]", text)
    assert "f32[1,8192,3840]" not in text and "f32[1,8192,1920]" not in text
    assert "f32[1,128,3840,128]" in text      # the chunk-start states
    # q, k, v padded, their gradients, o padded and the states (252 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def test_sparse_attention_layer_at_the_benchmark_cells_shape(
        one_chip, compiled_kernels):
    """ISSUE 59: keyevl2_train_s16384's attention sublayer (norm, q / k / v
    with their norms and rotation, the indexer, the exact top 2048 a query,
    the two kernels over the selection, the output projection) at ONE row
    of 16 384, forward and backward, for the described v5e: the two
    kernels by name (ISSUE 60: ONE backward, whose accumulators of a whole
    row Mosaic takes under the module's VMEM limit, and whose delta is no
    array any more); the selection as ONE byte a pair; and no float array
    of 16384 x 16384, a head or not: never the main attention's scores or
    probabilities, and the index scores a block of 512 queries at a time."""
    from ray_tpu.models import KeyeVL2, KeyeVL2Config

    sa = importlib.import_module("ray_tpu.ops.sparse_attention")
    model = KeyeVL2(KeyeVL2Config.keye_vl2_30b_a3b(
        n_layer=1, experts_held=16, vocab_size=18992, max_seq=16384))
    c = model.config
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    lp = {n.split(".", 2)[2]: jax.ShapeDtypeStruct(
        v.shape[1:], v.dtype, sharding=one_chip)
        for n, v in shapes.items() if n.startswith("0.")
        and not n.split(".")[-1].startswith(("e_", "w_router", "mlp_"))}
    x = jax.ShapeDtypeStruct((1, 16384, c.d_model), jnp.bfloat16,
                             sharding=one_chip)

    def loss(x, lp):
        return model._attn(x, lp, *model._angles(None, 16384)).astype(
            jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        x, lp).compile().as_text()
    calls = " ".join(line.split(" = ")[0] for line in text.splitlines()
                     if 'custom_call_target="tpu_custom_call"' in line)
    for name in sa.KERNEL_NAMES.values():
        assert name in calls, (name, calls)
    assert "sparse_attn_bwd_dq" not in calls
    bwd = [line.split(" custom-call(")[0] for line in text.splitlines()
           if re.match(r"\s*%?sparse_attn_bwd", line)]
    # delta, rows of [.., 1, S] floats, is no output
    assert len(bwd) == 1 and "f32[" not in bwd[0], bwd
    assert "s8[1,16384,16384]" in text               # the selection
    assert re.search(r"f32\[512,\d+\]", text)        # a block's index scores
    assert not re.findall(r"\b(?:f32|bf16|f16|f64)\[[\d,]*16384,16384\]",
                          text)
    assert not re.findall(r"\b(?:f32|bf16)\[[\d,]*16384,\d+,16384\]", text)


def test_lightning_attention_at_the_benchmark_cells_shape(one_chip,
                                                          compiled_kernels):
    """ISSUE 69: minicpmsala_train_s32768's recurrence, B=1, S=32768, 16
    heads of 128 on a state of 128 x 128, every head its own q and k
    (groups == heads), a constant decay a head handed in TRACED (inside the
    model it is a scanned layer's entry), chunks of 256, fed as the model
    feeds it: forward and backward are the two kernels by name, the states
    a chunk starts from are float32 [1, 16, 128, 128, 128], and NO array
    with two chunk-long dimensions beside a head and chunk axis (a [..,
    chunks, heads, 256, 256] score matrix) is in the compiled program; the
    decays' powers are [16, 256, 256], a table of the head."""
    la = importlib.import_module("ray_tpu.ops.lightning_attention")
    b, t, h = 1, 32768, 16
    sd = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)

    def loss(q, k, v, a):
        heads = lambda x: x.reshape(b, t, h, 128)  # noqa: E731
        return la.lightning_attention(
            heads(q), heads(k), heads(v), a,
            scale=128 ** -0.5).astype(jnp.float32).sum()

    before = la.PATH_COUNTS["kernel"]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sd((b, t, h * 128)), sd((b, t, h * 128)), sd((b, t, h * 128)),
        sd((h,), jnp.float32)).compile().as_text()
    assert la.PATH_COUNTS["kernel"] == before + 1
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    assert any(la.KERNEL_NAMES["fwd"] in c for c in calls)
    assert any(la.KERNEL_NAMES["bwd"] in c for c in calls)
    assert "f32[1,16,128,128,128]" in text
    assert not re.findall(r"\w+\[[\d,]*128,16,256,256\]", text)
    assert not re.findall(r"\w+\[[\d,]*16,128,256,256\]", text)


def test_block_sparse_attention_at_the_benchmark_cells_shape(
        one_chip, compiled_kernels):
    """ISSUE 69: minicpmsala_train_s32768's attention over a block
    selection at ONE row of 32 768, a group of 16 query heads of 128 on one
    key/value head, forward and backward, for the described v5e: the masked
    kernel pair by name in blocks of 512 x 512, which the backward's
    accumulators of a whole row admit under the module's VMEM limit
    (ROADMAP B25(h): 1024 x 1024 is refused by ``_bwd_vmem``); the selection
    as ONE byte a (query, key block), expanded to a byte a pair for the
    kernels; the pooled scores a block of 512 queries and one head at a
    time; and no float array of 32768 x 32768 or of 32768 x 2048 a head."""
    sa = importlib.import_module("ray_tpu.ops.sparse_attention")
    s, h = 32768, 16
    sd = lambda heads: jax.ShapeDtypeStruct(  # noqa: E731
        (1, s, heads, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return sa.block_sparse_attention(q, k, v).astype(jnp.float32).sum()

    before = sa.CALL_COUNTS["masked_flash"]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sd(h), sd(1), sd(1)).compile().as_text()
    assert sa.CALL_COUNTS["masked_flash"] == before + 1
    calls = " ".join(line.split(" = ")[0] for line in text.splitlines()
                     if 'custom_call_target="tpu_custom_call"' in line)
    for name in sa.KERNEL_NAMES.values():
        assert name in calls, (name, calls)
    assert "s8[1,1,32768,512]" in text or "s8[64,512,512]" in text
    assert "s8[1,32768,32768]" in text               # what the kernels read
    assert re.search(r"f32\[512,2048\]", text)       # a block's pooled scores
    assert not re.findall(r"\b(?:f32|bf16|f16|f64)\[[\d,]*32768,32768\]",
                          text)
    assert not re.findall(r"\bf32\[[\d,]*32768,2048\]", text)


def test_gpt2_small_train_step_at_smoke_batch(one_chip, compiled_kernels):
    """chip_smoke.py's trainer step: adamw on GPTConfig.small(bf16, flash),
    B=8, S=1024, params and optimizer state donated."""
    import optax

    from ray_tpu.models import GPT, GPTConfig

    model = GPT(GPTConfig.small(dtype=jnp.bfloat16, use_flash=True))
    tx = optax.adamw(3e-4, weight_decay=0.1)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(tx.init, params)

    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(model.loss)(params, tokens, targets)
        updates, opt_state = tx.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    tok = jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=one_chip)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        _on(one_chip, params), _on(one_chip, opt), tok, tok).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # params f32 + two adam moments, nothing else resident across steps
    assert 1.4e9 < fits(compiled) < 1.6e9


def test_gpt2_small_engine_programs(one_chip):
    """The serve engine's prefill (512 bucket) and decode (8 slots)
    programs at gpt2-small width over chip_smoke.py's KV pool. This path
    holds no Pallas kernel: paged prefill calls mha_reference and paged
    attention is plain jnp."""
    from ray_tpu.models import GPT, GPTConfig

    model = GPT(GPTConfig.small())
    params = _on(one_chip, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = _on(one_chip, jax.eval_shape(
        lambda: model.init_paged_cache(320, 16)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    prefill = jax.jit(model.paged_prefill).lower(
        params, cache, i32(1, 512), i32(), i32(40)).compile()
    assert "tpu_custom_call" not in prefill.as_text()
    fits(prefill)
    decode = jax.jit(model.paged_decode_step).lower(
        params, cache, i32(8), i32(8), i32(8, 40),
        jax.ShapeDtypeStruct((8,), jnp.bool_, sharding=one_chip)).compile()
    fits(decode)


def test_hlo_comparison_ignores_where_code_stands(one_chip,
                                                  compiled_kernels):
    """scripts/train_step_hlo.py compares two trees' programs with source
    locations stripped: the same function written on two different lines,
    flash kernels inside, compiles to texts that differ as they stand
    (metadata, the stack-frame tables, the MLIR inside each custom call)
    and are equal once stripped; another program stays different."""
    from ray_tpu.ops.flash_attention import flash_attention

    tool = hlo_tool()

    def here(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    def there(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    def other(q, k, v):
        return flash_attention(q, k, v, causal=False).astype(
            jnp.float32).sum()

    qkv = jax.ShapeDtypeStruct((2, 256, 2, 64), jnp.bfloat16,
                               sharding=one_chip)

    def text(f):
        f.__name__ = "loss"           # the name goes into every op_name
        return jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(
            qkv, qkv, qkv).compile().as_text()

    a, b, c = text(here), text(there), text(other)
    assert a != b
    assert tool.strip_locations(a) == tool.strip_locations(b)
    assert "tpu_custom_call" in tool.strip_locations(a)
    assert tool.strip_locations(a) != tool.strip_locations(c)


def _ops(name: str):
    # the module, not the function of its name that the package exports
    return importlib.import_module("ray_tpu.ops." + name)


def _small_flash(sd):
    return (lambda q, k, v: _ops("flash_attention").flash_attention(
        q, k, v, causal=True)), [sd((1, 256, 2, 64), jnp.bfloat16)] * 3


def _small_expert_layer(sd):
    p = {"w_router": sd((128, 4)), "router_bias": sd((4,))}
    p.update({n: sd((2, 128, 128)) for n in ("e_gate", "e_up", "e_down")})
    return (lambda x, p: _ops("expert_layer").held_expert_layer(
        x, p, experts_held=2, expert_offset=0, top_k=2,
        routed_scale=1.0)[0]), [sd((256, 128), jnp.bfloat16), p]


def _small_sparse(sd):
    bf = jnp.bfloat16
    return (lambda *a: _ops("sparse_attention").sparse_attention(
        *a, topk=64, q_chunk=128, block_q=128, block_k=128)), [
        sd((1, 256, 2, 128), bf), sd((1, 256, 1, 128), bf),
        sd((1, 256, 1, 128), bf), sd((1, 256, 2, 64), bf),
        sd((1, 256, 64), bf), sd((1, 256, 2))]


def _small_ssd(sd):
    bc = sd((1, 256, 1, 128), jnp.bfloat16)
    return (lambda *a: _ops("ssd_scan").ssd_scan(*a, chunk=128)), [
        sd((1, 256, 2, 64), jnp.bfloat16), sd((1, 256, 2)), sd((2,)), bc, bc,
        sd((2,))]


def _small_selective(sd):
    return _ops("selective_scan").selective_scan, [
        sd((1, 32, 128), jnp.bfloat16), sd((1, 32, 128)), sd((128, 8)),
        sd((1, 32, 8)), sd((1, 32, 8)), sd((128,))]


def _small_hyper_connection(sd):
    hc = _ops("hyper_connection")
    p = {k: sd(s) for k, s in hc.hc_param_shapes(4, 128).items()}
    return (lambda x, p: hc.hc_mix(
        x, p, lambda z: (jnp.tanh(z), None), iters=2, eps=1e-6,
        clamp=(-30.0, 30.0), rms_eps=1e-6)[0]), [
        tuple(sd((128, 128)) for _ in range(4)), p]


def _small_short_conv(sd):
    # squared: the forward keeps nothing for the backward, so a loss that
    # is linear in its output would leave the forward kernel dead
    return (lambda bcx, w: jnp.square(
        _ops("short_conv").in_proj_short_conv(bcx, w))), \
        [sd((1, 64, 384), jnp.bfloat16), sd((3, 128))]


def _small_lightning(sd):
    return (lambda q, k, v, a: _ops("lightning_attention").lightning_attention(
        q, k, v, a, scale=1.0, chunk=128)), \
        [sd((1, 256, 2, 128), jnp.bfloat16)] * 3 + [sd((2,))]


def _small_kda(sd):
    return (lambda *a: _ops("kda_scan").kda_scan(*a, scale=1.0)), \
        [sd((1, 128, 128), jnp.bfloat16)] * 3 + [sd((1, 128, 128)),
                                                 sd((1, 128, 1))]


# the kernel files of ``ray_tpu/ops`` and one small call of each that
# takes its kernel route
KERNEL_FILES = {
    "flash_attention": _small_flash, "expert_layer": _small_expert_layer,
    "sparse_attention": _small_sparse, "ssd_scan": _small_ssd,
    "selective_scan": _small_selective,
    "hyper_connection": _small_hyper_connection, "kda_scan": _small_kda,
    "lightning_attention": _small_lightning,
    "short_conv": _small_short_conv}


@pytest.mark.parametrize("module", sorted(KERNEL_FILES))
def test_every_kernel_file_asks_the_one_switch(one_chip, monkeypatch,
                                               module):
    """ISSUE 61: a small call of each kernel file, forward and backward,
    LOWERED for the described v5e (nothing is compiled): interpreted as the
    backend here says, it holds no ``tpu_custom_call``; with
    ``kernel_common.use_interpret`` alone steered, it holds its forward
    and its backward kernel. A file with a switch of its own, which a tool
    that steers the one would describe interpreted, fails the second."""
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    f, args = KERNEL_FILES[module](sd)

    def kernels() -> int:
        loss = lambda *a: sum(jnp.sum(o.astype(jnp.float32))  # noqa: E731
                              for o in jax.tree.leaves(f(*a)))
        return jax.jit(jax.grad(loss)).lower(*args).as_text().count(
            "tpu_custom_call")

    assert kernels() == 0
    monkeypatch.setattr(_ops("kernel_common"), "use_interpret", lambda: False)
    assert kernels() >= 2


def test_the_hlo_tool_steers_every_kernel(monkeypatch):
    """ISSUE 61: after ``scripts/train_step_hlo.py``'s own steering no
    kernel file answers "interpreted": a census or a comparison made with
    the tool describes the program the chip runs (before, the tool steered
    two of three switches, and ``keyevl2_train_s16384``'s sparse kernels
    were compiled interpreted)."""
    common = _ops("kernel_common")
    assert common.use_interpret()                       # the CPU, here
    monkeypatch.setattr(common, "use_interpret", common.use_interpret)
    hlo_tool().steer_kernels()
    for module in KERNEL_FILES:
        assert _ops(module).kernel_common.use_interpret() is False, module


def test_no_kernel_file_imports_anothers_private_name():
    """ISSUE 61: no file of ``ray_tpu/ops`` takes a name that starts with
    ``_`` from another file of the package, by ``from .x import _y`` or as
    ``x._y`` of a module it imported: what several kernel files need is
    public in ``kernel_common.py``, and a rename inside one file breaks no
    other."""
    ops = os.path.dirname(_ops("kernel_common").__file__)
    taken = []
    for name in sorted(os.listdir(ops)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(ops, name)) as f:
            tree = ast.parse(f.read())
        siblings = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.level == 1:
                for a in node.names:
                    if node.module is None:         # from . import x
                        siblings.add(a.asname or a.name)
                    elif a.name.startswith("_"):
                        taken.append(f"{name}: {node.module}.{a.name}")
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) and node.attr.startswith("_") \
                    and not node.attr.startswith("__") \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id in siblings:
                taken.append(f"{name}: {node.value.id}.{node.attr}")
    assert not taken, taken
