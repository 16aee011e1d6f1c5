"""ISSUE 24: the names a device trace shows are part of the measurement.
The four engine programs (``jit__decode``, ``jit__prefill``,
``jit__extend``, ``jit__cow``: the benchmark's readers match them) on both
lowering paths, the five flash kernels, and the model's named scopes in
the ``op_name`` of the operations a loss lowers to."""
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (DeepseekV3, DeepseekV3Config, GPT, GPTConfig,
                            GraniteHybrid, GraniteHybridConfig, KeyeVL2,
                            KeyeVL2Config, KimiLinear, KimiLinearConfig,
                            Lfm2Moe, Lfm2MoeConfig, Llama, LlamaConfig,
                            NemotronH, NemotronHConfig,
                            Qwen3Next, Qwen3NextConfig, SambaY, SambaYConfig)
import importlib

fa = importlib.import_module("ray_tpu.ops.flash_attention")  # the module
el = importlib.import_module("ray_tpu.ops.expert_layer")
ssd = importlib.import_module("ray_tpu.ops.ssd_scan")
sel = importlib.import_module("ray_tpu.ops.selective_scan")
kda = importlib.import_module("ray_tpu.ops.kda_scan")
hc = importlib.import_module("ray_tpu.ops.hyper_connection")
sa = importlib.import_module("ray_tpu.ops.sparse_attention")
sc = importlib.import_module("ray_tpu.ops.short_conv")
from ray_tpu.serve.llm import EngineConfig, LLMEngine, build_model

PROGRAMS = ("_decode", "_prefill", "_extend", "_cow")


@pytest.fixture(scope="module")
def engines():
    m, params = build_model("gpt-tiny")
    cfg = dict(block_size=4, num_blocks=32, max_batch=4,
               max_blocks_per_seq=8, prefill_buckets=(8, 16))
    return {tp: LLMEngine(m, params, EngineConfig(tp=tp, **cfg),
                          name=f"names-tp{tp}") for tp in (1, 2)}


def _program_args(eng, which):
    cfg = eng.config
    kc, vc = eng._cache["k"], eng._cache["v"]
    b, m = cfg.max_batch, cfg.max_blocks_per_seq
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    return {
        "_decode": (eng.params, kc, vc, i32(b), i32(b), i32(b, m),
                    jnp.zeros((b,), bool)),
        "_prefill": (eng.params, kc, vc, i32(1, 8), jnp.int32(3), i32(m)),
        "_extend": (eng.params, kc, vc, i32(1, 8), jnp.int32(4),
                    jnp.int32(3), i32(m)),
        "_cow": (kc, vc, jnp.int32(0), jnp.int32(1)),
    }[which]


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("which", PROGRAMS)
def test_engine_program_names_are_pinned(engines, which, tp):
    eng = engines[tp]
    fn = getattr(eng, which + "_fn")
    text = fn.lower(*_program_args(eng, which)).as_text()
    # what a profiler's "XLA Modules" line calls the program
    assert re.search(r"module @jit_" + which + r"\b", text), text[:200]


def _fwd_bwd_text(seq, block):
    q = jnp.zeros((1, seq, 2, 64), jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, block_q=block,
                                  block_k=block).astype(jnp.float32).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).as_text(debug_info=True)


@pytest.fixture(scope="module")
def kernel_texts():
    # one K/V block: the single-block forward and the fused backward;
    # two: the streamed forward and the two-pass backward
    return {1: _fwd_bwd_text(128, 128), 2: _fwd_bwd_text(256, 128)}


@pytest.mark.parametrize("key,name,blocks", [
    ("fwd_single", "flash_fwd_single", 1),
    ("bwd_fused", "flash_bwd_fused", 1),
    ("fwd", "flash_fwd", 2),
    ("bwd_dq", "flash_bwd_dq", 2),
    ("bwd_dkv", "flash_bwd_dkv", 2),
])
def test_flash_kernel_names_are_pinned(kernel_texts, key, name, blocks):
    assert fa.KERNEL_NAMES[key] == name
    assert len(set(fa.KERNEL_NAMES.values())) == 5
    # the name is on the name stack of what the kernel lowers to here
    # (interpret mode), and is the kernel's name in a compiled program
    # (tests/test_chip_compile.py)
    pattern = r"[/\"(]" + name + r"[/\")]"
    assert re.search(pattern, kernel_texts[blocks])
    assert not re.search(pattern, kernel_texts[3 - blocks])


@pytest.fixture(scope="module")
def latent_text():
    """Forward and backward of a causal latent call as lowered here.
    ISSUE 46: one block of 256, so its kernels are the banded ones (two
    bands of 128)."""
    q = jnp.zeros((1, 256, 2, 128), jnp.bfloat16)
    qr, kr = jnp.zeros((1, 256, 2, 64), q.dtype), jnp.zeros((1, 256, 64),
                                                             q.dtype)

    def loss(q, k, v, qr, kr):
        return fa.flash_attention(q, k, v, causal=True, block_q=256,
                                  block_k=256, q_rope=qr,
                                  k_rope=kr).astype(jnp.float32).sum()

    before = fa.PATH_COUNTS["latent"], fa.BAND_COUNTS[2]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        q, q, q, qr, kr).as_text(debug_info=True)
    assert (fa.PATH_COUNTS["latent"], fa.BAND_COUNTS[2]) == (
        before[0] + 1, before[1] + 1)
    return text


@pytest.mark.parametrize("key,name", [
    ("fwd", "flash_latent_fwd"), ("bwd_dkv", "flash_latent_bwd_dkv")])
def test_latent_flash_kernel_names_are_pinned(latent_text, key, name):
    """ISSUE 33: ``mla_attention_roofline`` finds its kernels by these.
    ISSUE 34, 48: the ONE backward is launched as ``flash_latent_bwd_dkv``
    (a name the reader's pattern knows)."""
    assert fa.LATENT_KERNEL_NAMES[key] == name
    assert len(set(fa.LATENT_KERNEL_NAMES.values())) == 2
    assert not set(fa.LATENT_KERNEL_NAMES.values()) & set(
        fa.KERNEL_NAMES.values())
    assert re.search(r"[/\"(]" + name + r"[/\")]", latent_text)
    # no kernel of the one-part score is in a latent call
    for other in fa.KERNEL_NAMES.values():
        assert not re.search(r"[/\"(]" + other + r"[/\")]", latent_text)


@pytest.mark.parametrize("key,name", [("rows", "grouped_matmul"),
                                      ("weights", "grouped_matmul_dw")])
def test_grouped_matmul_kernel_names_are_pinned(key, name):
    """ISSUE 33: ``grouped_matmul_roofline`` finds its kernels by these."""
    assert el.KERNEL_NAMES[key] == name
    x, w = jnp.zeros((32, 16), jnp.bfloat16), jnp.zeros((2, 16, 8),
                                                        jnp.bfloat16)
    at = el.sort_rows(jnp.zeros((8, 2), jnp.int32), 2, 0, 32, 8)
    text = jax.jit(jax.grad(lambda x, w: el.grouped_matmul(
        x, w, at["tile_expert"], at["n_used"], 8).astype(
        jnp.float32).sum(), argnums=(0, 1))).lower(x, w).as_text(
        debug_info=True)
    assert re.search(r"[/\"(]" + name + r"[/\")]", text)


def _pallas_calls(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, found)
    return found


@pytest.mark.parametrize("expert", ["swiglu", "relu2"])
@pytest.mark.parametrize("key,name", [("hidden", "expert_hidden_fwd"),
                                      ("hidden_bwd", "expert_hidden_bwd")])
def test_expert_hidden_kernel_names_are_pinned(key, name, expert):
    """ISSUE 68: the experts' first half is a kernel pair of these names,
    beside the grouped product's two, under the layer's ``experts`` scope
    (what ``train_experts_ms`` charges them to), for either kind of expert.
    The layer's unfused first half (a grouped product a matrix and passes
    over the row buffer) is gone: forward and backward of a layer hold ONE
    call of each of the pair, ``e_down``'s grouped product and its
    transpose, and a ``grouped_matmul_dw`` a matrix."""
    import collections

    assert el.KERNEL_NAMES[key] == name
    d, f, held = 16, 8, 2
    p = {"w_router": jnp.ones((d, 4)), "router_bias": jnp.zeros((4,)),
         "e_up": jnp.ones((held, d, f)), "e_down": jnp.ones((held, f, d))}
    if expert == "swiglu":
        p["e_gate"] = jnp.ones((held, d, f))
    grad = jax.grad(lambda x, p: el.held_expert_layer(
        x, p, experts_held=held, expert_offset=0, top_k=2, routed_scale=1.0,
        expert=expert, tile=8)[0].astype(jnp.float32).sum(), argnums=(0, 1))
    x = jnp.ones((8, d), jnp.bfloat16)
    text = jax.jit(grad).lower(x, p).as_text(debug_info=True)
    assert re.search(r"\(experts\)+/" + name + "/pallas_call", text)
    assert _pallas_calls(jax.make_jaxpr(grad)(x, p).jaxpr,
                         collections.Counter()) == {
        "expert_hidden_fwd": 1, "expert_hidden_bwd": 1, "grouped_matmul": 2,
        "grouped_matmul_dw": 3 if expert == "swiglu" else 2}


@pytest.mark.parametrize("key,name", [("fwd", "ssd_chunk_fwd"),
                                      ("bwd", "ssd_chunk_bwd")])
def test_ssd_scan_kernel_names_are_pinned(key, name):
    """ISSUE 36: ``ssd_scan_roofline`` finds its kernels by these."""
    assert ssd.KERNEL_NAMES[key] == name
    x = jnp.zeros((1, 256, 2, 64), jnp.bfloat16)
    bc = jnp.zeros((1, 256, 1, 128), jnp.bfloat16)
    text = jax.jit(jax.grad(lambda x, dt, bm, cm: ssd.ssd_scan(
        x, dt, -jnp.ones((2,)), bm, cm, jnp.ones((2,)), chunk=128).astype(
        jnp.float32).sum(), argnums=(0, 1, 2, 3))).lower(
        x, jnp.ones((1, 256, 2)), bc, bc).as_text(debug_info=True)
    pattern = r"[/\"(]" + name + r"[/\")]"
    assert re.search(pattern, text)
    # a call whose shapes do not tile holds neither kernel
    plain = jax.jit(lambda x, dt, bm, cm: ssd.ssd_scan(
        x, dt, -jnp.ones((2,)), bm, cm, jnp.ones((2,)), chunk=96)).lower(
        x, jnp.ones((1, 256, 2)), bc, bc).as_text(debug_info=True)
    assert not re.search(pattern, plain)


def test_the_expert_layer_and_the_latent_route_leave_their_events():
    """ISSUE 33: ``rtpu.ops.expert_layer`` at trace time (experts held, of
    how many, top k, the row buffer) and ``rtpu.ops.flash.path`` with the
    head sizes and the shared key of a latent call."""
    from ray_tpu.perf.recorder import get_recorder

    m = DeepseekV3(DeepseekV3Config.tiny(experts_held=2, expert_offset=4))
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    before = el.LAYER_COUNTS[(2, 8)], fa.PATH_COUNTS["latent"]
    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    try:
        jax.jit(m.loss).lower(p, toks, toks)
        events = rec.snapshot()
    finally:
        rec.enabled = was
    assert el.LAYER_COUNTS[(2, 8)] == before[0] + 1
    assert fa.PATH_COUNTS["latent"] > before[1]
    layer = [e for e in events if e["kind"] == "rtpu.ops.expert_layer"][-1]
    assert layer["data"] == {
        "experts_held": 2, "of": 8, "top_k": 3, "expert_offset": 4,
        # ISSUE 57: the slots of a token's pairs, min(top k, experts held)
        "pair_slots": 2,
        # ISSUE 65: the pairs choice-major, a token's slots down axis 0
        "slot_axis": 0,
        "tokens": 256, "row_buffer": el.buffer_rows(256, 3, 2),
        "row_tile": el.ROW_TILE,
        # ISSUE 52: how the router scores, whether the shared expert is gated
        "score": "sigmoid", "shared": True, "shared_gate": False,
        # ISSUE 56: the kind of every MLP of the layer, the latent's width
        "expert": "swiglu", "latent": 0,
        # ISSUE 68: the first half is the kernel pair, all of F a block
        "mlp_in": "kernel", "mlp_in_block": 32}
    path = [e for e in events if e["kind"] == "rtpu.ops.flash.path"
            and e["label"] == "latent"][-1]
    assert path["data"]["hd_qk"] == 192 and path["data"]["hd_v"] == 128
    assert path["data"]["shared_key"] == 64 and path["data"]["S"] == 128
    # ISSUE 46: the bands of a diagonal step (a block of 128 is one)
    assert path["data"]["layout"] == "latent"
    assert path["data"]["bands"] == 1

@pytest.mark.parametrize("key,name", [("fwd", "selscan_chunk_fwd"),
                                      ("bwd", "selscan_chunk_bwd")])
def test_selective_scan_kernel_names_are_pinned(key, name):
    """ISSUE 43: ``selective_scan_roofline`` finds its kernels by these."""
    assert sel.KERNEL_NAMES[key] == name

    def lowered(channels):
        x = jnp.zeros((1, 64, channels), jnp.bfloat16)
        bc = jnp.zeros((1, 64, 16), jnp.bfloat16)
        return jax.jit(jax.grad(lambda x, dt, bm, cm: sel.selective_scan(
            x, dt, -jnp.ones((channels, 16)), bm, cm, jnp.ones((channels,)),
            chunk=32).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3))).lower(
            x, jnp.ones((1, 64, channels)), bc, bc).as_text(debug_info=True)

    pattern = r"[/\"(]" + name + r"[/\")]"
    assert re.search(pattern, lowered(128))
    # a call whose channels do not tile the lanes holds neither kernel
    assert not re.search(pattern, lowered(96))


def test_the_delta_rule_scan_and_its_stack_leave_their_events():
    """ISSUES 49, 50, 51: ``rtpu.ops.kda.path`` at trace time, once a KDA
    layer's body (route, chunk, tokens, heads, padded tokens; the tiny
    preset's heads of 128 take the kernel pair, two heads a program, and
    the model's call, ``kda_gated_scan``, has the kernels make the norms
    of q and k and the gate: ``prologue: in_kernel``; a ``kda_scan`` call
    with g ready says ``jnp``, ``tests/test_kda_scan.py``), and
    ``rtpu.models.stack.runs`` with the runs the model walked and what each
    keeps (a KDA run its layers' inputs alone). The kernels' names are
    pinned: a device trace and the compiled HLO show them, and
    ``kda_scan_roofline`` finds its time by the scope ``scan`` they run
    under."""
    from ray_tpu.perf.recorder import get_recorder

    # ISSUE 53: the body for one decay a head is a pair of its own names, so
    # a trace's breakdown tells the two rules apart
    assert kda.KERNEL_NAMES == {"fwd": "kda_chunk_fwd",
                                "bwd": "kda_chunk_bwd",
                                "gdn_fwd": "gdn_chunk_fwd",
                                "gdn_bwd": "gdn_chunk_bwd"}
    m = KimiLinear(KimiLinearConfig.tiny(experts_held=2))
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 150), jnp.int32)
    before = kda.PATH_COUNTS.copy()
    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    try:
        text = jax.jit(jax.grad(m.loss)).lower(p, toks, toks).as_text(
            debug_info=True)
        events = rec.snapshot()
    finally:
        rec.enabled = was
    gained = kda.PATH_COUNTS - before
    assert set(gained) == {"kernel"} and gained["kernel"] >= 3  # three runs
    path = [e for e in events if e["kind"] == "rtpu.ops.kda.path"][-1]
    assert path["label"] == "kernel"
    assert path["data"] == {
        "route": "kernel", "chunk": 64, "tokens": 150,
        "padded_tokens": 42, "heads": 2, "d_k": 128, "d_v": 128,
        "chunks": 3, "heads_per_block": 2, "prologue": "in_kernel",
        # ISSUE 66: the pairs' solves a program issues side by side
        "pairs_in_step": 1,
        # ISSUE 52: KDA's decay is one a key channel, a key head a value head
        "decay": "channel", "key_heads": 2,
        # ISSUE 53: the body that makes the decayed scores in sub-blocks
        "body": "channel_decay",
        # ISSUE 67: the lanes a head occupies in what the route reads (the
        # head sizes: whole tiles), the rows of the solve's Neumann blocks
        "lanes_k": 128, "lanes_v": 128, "solve_block": 8}
    # both kernels stand under the scope the roofline reads, and the other
    # rule's are not in this program
    for part in ("fwd", "bwd"):
        name = kda.KERNEL_NAMES[part]
        assert re.search(r"scan/[^\n]*" + name, text), name
        assert kda.KERNEL_NAMES["gdn_" + part] not in text
    runs = [e for e in events if e["kind"] == "rtpu.models.stack.runs"
            and e["label"] == "kimi_linear"][-1]
    assert runs["data"]["runs"] == [["kda_dense", 1], ["kda_moe", 2],
                                    ["mla_moe", 1], ["kda_moe", 1]]
    assert runs["data"]["kept"] == [
        [], [], ["flash_out", "flash_lse", "attn_q"], []]
    assert runs["data"]["side_state_bytes"] == 0


def test_a_gated_deltanet_stack_leaves_its_events():
    """ISSUES 52, 53: what a Qwen3-Next shaped loss leaves at trace time.
    ``rtpu.ops.kda.path``: the kernel route with ``decay`` ``head`` (one a
    head) through the ``body`` ``head_decay`` (the decay factored out of
    the scores; PR 52 ran it through ``channel_decay``), a program the 4
    value heads over the 2 ``key_heads`` they read, the norms and the gate
    made in the kernels;
    ``rtpu.ops.expert_layer``: ``score`` ``softmax`` and ``shared_gate``;
    ``rtpu.ops.flash.path``: heads of 256 on the ``relayout`` route;
    ``rtpu.models.stack.runs``: 3 scanned Gated DeltaNet layers that keep
    their inputs alone, then the attention layer with the flash kernels'
    output and row statistics. The body's own two kernels (``gdn_chunk_fwd``
    / ``gdn_chunk_bwd``) stand under the scope ``scan``, where
    ``gdn_scan_roofline`` finds their time, and KDA's are not in the
    program."""
    from ray_tpu.perf.recorder import get_recorder

    m = Qwen3Next(Qwen3NextConfig.tiny(experts_held=2, expert_offset=4))
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    before = kda.PATH_COUNTS.copy()
    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    try:
        text = jax.jit(jax.grad(m.loss)).lower(p, toks, toks).as_text(
            debug_info=True)
        events = rec.snapshot()
    finally:
        rec.enabled = was
    assert set(kda.PATH_COUNTS - before) == {"kernel"}
    last = lambda kind: [e for e in events if e["kind"] == kind][-1]  # noqa: E731
    assert last("rtpu.ops.kda.path")["data"] == {
        "route": "kernel", "chunk": 64, "tokens": 256, "padded_tokens": 0,
        "heads": 4, "d_k": 128, "d_v": 128, "chunks": 4,
        "heads_per_block": 4, "prologue": "in_kernel", "decay": "head",
        "key_heads": 2, "body": "head_decay", "pairs_in_step": 2,
        "lanes_k": 128, "lanes_v": 128, "solve_block": 8}    # ISSUE 67
    assert last("rtpu.ops.expert_layer")["data"] == {
        "experts_held": 2, "of": 8, "top_k": 3, "expert_offset": 4,
        # ISSUE 57: the slots of a token's pairs, min(top k, experts held)
        "pair_slots": 2,
        # ISSUE 65: the pairs choice-major, a token's slots down axis 0
        "slot_axis": 0,
        "tokens": 512, "row_buffer": el.buffer_rows(512, 3, 2),
        "row_tile": el.ROW_TILE, "score": "softmax", "shared": True,
        "shared_gate": True,
        "expert": "swiglu", "latent": 0,
        # ISSUE 68: the first half is the kernel pair, all of F a block
        "mlp_in": "kernel", "mlp_in_block": 32}
    flash = last("rtpu.ops.flash.path")
    assert flash["label"] == "relayout" and flash["data"]["hd"] == 256
    for part in ("fwd", "bwd"):
        name = kda.KERNEL_NAMES["gdn_" + part]
        assert re.search(r"scan/[^\n]*" + name, text), name
        assert kda.KERNEL_NAMES[part] not in text
    runs = [e for e in events if e["kind"] == "rtpu.models.stack.runs"
            and e["label"] == "qwen3_next"][-1]
    assert runs["data"]["runs"] == [["gdn_moe", 3], ["attn_moe", 1]]
    assert runs["data"]["kept"] == [[], ["flash_out", "flash_lse"]]
    assert runs["data"]["side_state_bytes"] == 0


def test_a_share_of_an_olmo_hybrid_stack_leaves_its_events():
    """ISSUE 67: what an Olmo-Hybrid shaped loss leaves at trace time.
    ``rtpu.ops.kda.path``: the kernel route with ``decay`` ``head`` at the
    MODEL's head sizes, ``d_k`` 96 and ``d_v`` 192, and the two facts this
    PR adds, ``lanes_k`` 128 and ``lanes_v`` 256 (the lanes a head's keys
    and values occupy in what the kernels read: ``gdn_lane_fill_pct`` is
    100 x 288 / 384), the solve in blocks of 4 (beta reaches 2), one key
    head and its two value tiles a program; ``rtpu.ops.flash.path``: heads
    of 128 read from the merged arrays with no copy; ``rtpu.models.stack.
    runs``: 3 scanned Gated DeltaNet layers that keep their inputs alone,
    then the attention layer with the flash kernels' output and row
    statistics, and the share of the heads held. No new kernel name: the
    pair is Gated DeltaNet's (``gdn_chunk_fwd`` / ``gdn_chunk_bwd``), under
    the scope ``scan``, and the one-part flash kernels under ``attn``."""
    from ray_tpu.models import OlmoHybrid, OlmoHybridConfig
    from ray_tpu.perf.recorder import get_recorder

    m = OlmoHybrid(OlmoHybridConfig.tiny(n_head=4, heads_held=3,
                                         head_offset=1))
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    before = kda.PATH_COUNTS.copy()
    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    try:
        text = jax.jit(jax.grad(m.loss)).lower(p, toks, toks).as_text(
            debug_info=True)
        events = rec.snapshot()
    finally:
        rec.enabled = was
    assert set(kda.PATH_COUNTS - before) == {"kernel"}
    last = lambda kind: [e for e in events if e["kind"] == kind][-1]  # noqa: E731
    assert last("rtpu.ops.kda.path")["data"] == {
        "route": "kernel", "chunk": 64, "tokens": 256, "padded_tokens": 0,
        "heads": 3, "d_k": 96, "d_v": 192, "lanes_k": 128, "lanes_v": 256,
        "chunks": 4, "solve_block": 4, "heads_per_block": 2,
        "pairs_in_step": 1, "prologue": "in_kernel", "decay": "head",
        "key_heads": 3, "body": "head_decay"}
    flash = last("rtpu.ops.flash.path")
    assert flash["label"] != "relayout" and flash["data"]["hd"] == 128
    for part in ("fwd", "bwd"):
        name = kda.KERNEL_NAMES["gdn_" + part]
        assert re.search(r"scan/[^\n]*" + name, text), name
        assert kda.KERNEL_NAMES[part] not in text
    assert re.search(r"attn/[^\n]*flash", text)
    runs = [e for e in events if e["kind"] == "rtpu.models.stack.runs"
            and e["label"] == "olmo_hybrid"][-1]
    assert runs["data"]["runs"] == [["gdn", 3], ["attn", 1]]
    assert runs["data"]["kept"] == [[], ["flash_out", "flash_lse"]]
    assert runs["data"]["heads"] == [3, 4]
    assert runs["data"]["head_offset"] == 1


def test_a_share_of_a_latent_expert_stack_leaves_its_events():
    """ISSUE 56: what a Nemotron-H shaped loss leaves at trace time.
    ``rtpu.models.nemotron_h.share``: the groups, heads, experts and
    vocabulary rows held and of how many; ``rtpu.ops.expert_layer``: the
    ``expert`` kind ``relu2`` and the ``latent`` width; ``rtpu.ops.ssd.path``:
    the kernel route for the ONE group held; ``rtpu.models.stack.runs``: two
    scanned (``moe``, ``mamba``) periods that keep their inputs alone, then
    the attention layer with the flash kernels' output and row statistics.
    The scan's kernels stand under the scope ``scan``."""
    from ray_tpu.perf.recorder import get_recorder

    m = NemotronH(NemotronHConfig.tiny(
        experts_held=2, expert_offset=4, mamba_groups_held=1,
        mamba_group_offset=1, heads_held=2, head_offset=2))
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    try:
        text = jax.jit(jax.grad(m.loss)).lower(p, toks, toks).as_text(
            debug_info=True)
        events = rec.snapshot()
    finally:
        rec.enabled = was
    last = lambda kind: [e for e in events if e["kind"] == kind][-1]  # noqa: E731
    share = last("rtpu.models.nemotron_h.share")
    assert share["label"] == "held" and share["data"] == {
        "mamba_groups": [1, 2], "mamba_group_offset": 1,
        "mamba_heads": [2, 4], "query_heads": [2, 4], "head_offset": 2,
        "kv_heads": [1, 2], "experts": [2, 8], "expert_offset": 4,
        "vocab_rows": 512}
    assert last("rtpu.ops.expert_layer")["data"] == {
        "experts_held": 2, "of": 8, "top_k": 3, "expert_offset": 4,
        # ISSUE 57: the slots of a token's pairs, min(top k, experts held)
        "pair_slots": 2,
        # ISSUE 65: the pairs choice-major, a token's slots down axis 0
        "slot_axis": 0,
        "tokens": 256, "row_buffer": el.buffer_rows(256, 3, 2),
        "row_tile": el.ROW_TILE, "score": "sigmoid", "shared": True, "shared_gate": False,
        "expert": "relu2", "latent": 32,
        # ISSUE 68: the first half is the kernel pair, all of F a block
        "mlp_in": "kernel", "mlp_in_block": 48}
    path = last("rtpu.ops.ssd.path")
    assert path["label"] == "kernel" and path["data"]["groups"] == 1 \
        and path["data"]["heads"] == 2
    for part in ("fwd", "bwd"):
        assert re.search(r"scan/[^\n]*" + ssd.KERNEL_NAMES[part], text)
    runs = [e for e in events if e["kind"] == "rtpu.models.stack.runs"
            and e["label"] == "nemotron_h"][-1]
    assert runs["data"]["runs"] == [["moe+mamba", 2], ["attention", 1]]
    assert runs["data"]["kept"] == [[], ["flash_out", "flash_lse"]]
    assert runs["data"]["side_state_bytes"] == 0


@pytest.fixture(scope="module")
def sparse_stack():
    """A Keye-VL-2.0 shaped loss lowered (forward and backward) at S 256,
    four times its selection of 64 -> (text, events)."""
    from ray_tpu.perf.recorder import get_recorder

    m = KeyeVL2(KeyeVL2Config.tiny(experts_held=2, expert_offset=4))
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    try:
        text = jax.jit(jax.grad(m.loss)).lower(p, toks, toks).as_text(
            debug_info=True)
        events = rec.snapshot()
    finally:
        rec.enabled = was
    return text, events


def test_a_sparse_attention_stack_leaves_its_events(sparse_stack):
    """ISSUE 59: what a Keye-VL-2.0 shaped loss leaves at trace time.
    ``rtpu.ops.sparse_attention`` / ``selected``: the row, the selection,
    the indexer's heads, the ``route`` that computes the attention, which
    ``backward`` it has and the products a block pair and query head costs
    it (ISSUE 60: ``fused``, 5), what that backward is handed (``saved``)
    and the selected beside the causal pairs of a row;
    ``rtpu.models.keye_vl2.share``: the experts and vocabulary
    rows held and of how many; ``rtpu.ops.expert_layer``: ``shared`` False;
    ``rtpu.models.stack.runs``: ONE scanned run of like layers that keeps
    the selection and the kernels' output and row statistics."""
    _, events = sparse_stack
    last = lambda kind: [e for e in events if e["kind"] == kind][-1]  # noqa: E731
    sel = last("rtpu.ops.sparse_attention")
    assert sel["label"] == "selected" and sel["data"] == {
        "seq": 256, "topk": 64, "index_heads": 4, "index_dim": 64,
        "heads": 4, "kv_heads": 2, "head_dim": 128,
        "route": "masked_flash", "backward": "fused", "bwd_products": 5,
        "saved": "mask_int8", "q_chunk": 64,
        "selected_pairs": 64 * 65 // 2 + 192 * 64,
        "causal_pairs": 256 * 257 // 2}
    share = last("rtpu.models.keye_vl2.share")
    assert share["label"] == "held" and share["data"] == {
        "experts": [2, 8], "expert_offset": 4, "vocab_rows": 512,
        "layers": 2}
    assert last("rtpu.ops.expert_layer")["data"] == {
        "experts_held": 2, "of": 8, "top_k": 3, "expert_offset": 4,
        "pair_slots": 2, "slot_axis": 0, "tokens": 512,
        "row_buffer": el.buffer_rows(512, 3, 2), "row_tile": el.ROW_TILE,
        "score": "softmax", "shared": False, "shared_gate": False,
        "expert": "swiglu", "latent": 0,
        # ISSUE 68: the first half is the kernel pair, all of F a block
        "mlp_in": "kernel", "mlp_in_block": 32}
    runs = [e for e in events if e["kind"] == "rtpu.models.stack.runs"
            and e["label"] == "keye_vl2"][-1]
    assert runs["data"]["runs"] == [["attn_moe", 2]]
    assert runs["data"]["kept"] == [["sparse_mask", "sparse_out",
                                     "sparse_lse", "flash_out",
                                     "flash_lse"]]
    assert runs["data"]["side_state_bytes"] == 0


@pytest.mark.parametrize("key,name", [("fwd", "sparse_attn_fwd"),
                                      ("bwd", "sparse_attn_bwd_dkv")])
def test_sparse_attention_kernel_names_are_pinned(sparse_stack, key, name):
    """ISSUE 59: ``sparse_attention_roofline`` finds its kernels by these,
    and they stand under the scope ``attn``. ISSUE 60: the backward is ONE
    kernel under the name the reader already had: no ``sparse_attn_bwd_dq``
    stands in the text, and the scanned run's layer holds ONE backward call
    beside its one forward call (kept: not run again)."""
    text, _ = sparse_stack
    assert sa.KERNEL_NAMES[key] == name
    assert sorted(sa.KERNEL_NAMES.values()) == ["sparse_attn_bwd_dkv",
                                                "sparse_attn_fwd"]
    assert re.search(r"attn/[^\n\"]*" + name + r"[/\")]", text), name
    assert "sparse_attn_bwd_dq" not in text
    calls = re.findall(r"(sparse_attn_\w+)/pallas_call\"", text)
    assert sorted(calls) == ["sparse_attn_bwd_dkv", "sparse_attn_fwd"], calls
    for other in fa.KERNEL_NAMES.values():      # no dense flash kernel
        assert not re.search(r"[/\"(]" + other + r"[/\")]", text)


@pytest.mark.parametrize("scope", ["indexer", "select"])
def test_the_indexer_and_the_selection_have_scopes_and_no_backward(
        sparse_stack, scope):
    """ISSUE 59: ``train_indexer_ms`` and ``train_select_ms`` read these
    scopes (the innermost on an operation's name stack, under ``attn``). No
    gradient passes the selection, so neither stands under a transpose."""
    text, _ = sparse_stack
    names = set(re.findall(r'loc\("([^"]+)"', text))
    mine = [n for n in names if re.search(r"(^|/)" + scope + "/", n)]
    assert mine, scope
    assert [n for n in mine if re.search(r"attn/(.*/)?" + scope + "/", n)]
    assert not [n for n in mine if "transpose(" in n], scope


@pytest.fixture(scope="module")
def conv_stack():
    """An LFM2-MoE shaped loss with its balancing term lowered (forward and
    backward) at S 128 -> (text, events)."""
    from ray_tpu.perf.recorder import get_recorder

    m = Lfm2Moe(Lfm2MoeConfig.tiny(experts_held=2, expert_offset=4,
                                   router_aux_coef=0.001))
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    try:
        text = jax.jit(jax.grad(m.loss)).lower(p, toks, toks).as_text(
            debug_info=True)
        events = rec.snapshot()
    finally:
        rec.enabled = was
    return text, events


def test_a_gated_convolution_stack_leaves_its_events(conv_stack):
    """ISSUE 64: what an LFM2-MoE shaped loss leaves at trace time.
    ``rtpu.ops.short_conv`` (beside ``rtpu.ops.conv``'s), once a traced
    call: tokens, channels, taps and the route, ``kernel`` here;
    ``rtpu.models.lfm2_moe.share``: the experts and vocabulary rows held
    and the layers' kinds; ``rtpu.ops.expert_layer``: a sigmoid router
    without a shared expert; ``rtpu.models.stack.runs``: three runs, the
    attention layer alone keeping its kernels' output and statistics."""
    _, events = conv_stack
    last = lambda kind: [e for e in events if e["kind"] == kind][-1]  # noqa: E731
    conv = last("rtpu.ops.short_conv")
    assert conv["label"] == "kernel" and conv["data"] == {
        "route": "kernel", "tokens": 256, "channels": 128, "taps": 3}
    share = last("rtpu.models.lfm2_moe.share")
    assert share["label"] == "held" and share["data"] == {
        "experts": [2, 8], "expert_offset": 4, "vocab_rows": 512,
        "kinds": ["conv_mlp", "attn_moe", "conv_moe", "conv_moe"]}
    assert last("rtpu.ops.expert_layer")["data"] == {
        "experts_held": 2, "of": 8, "top_k": 3, "expert_offset": 4,
        "pair_slots": 2, "slot_axis": 0, "tokens": 256,
        "row_buffer": el.buffer_rows(256, 3, 2), "row_tile": el.ROW_TILE,
        "score": "sigmoid", "shared": False, "shared_gate": False,
        "expert": "swiglu", "latent": 0,
        # ISSUE 68: the first half is the kernel pair, all of F a block
        "mlp_in": "kernel", "mlp_in_block": 64}
    runs = [e for e in events if e["kind"] == "rtpu.models.stack.runs"
            and e["label"] == "lfm2_moe"][-1]
    assert runs["data"]["runs"] == [["conv_mlp", 1], ["attn_moe", 1],
                                    ["conv_moe", 2]]
    assert runs["data"]["kept"] == [[], ["flash_out", "flash_lse"], []]
    assert runs["data"]["side_state_bytes"] == 0


@pytest.mark.parametrize("key,name", [("fwd", "short_conv_fwd"),
                                      ("bwd", "short_conv_bwd")])
def test_short_conv_kernel_names_are_pinned(conv_stack, key, name):
    """ISSUE 64: ``short_conv_roofline`` finds its kernels by these, and
    they stand under the scope ``conv`` (``train_conv_ms`` reads it): the
    two gates and the taps are inside them, so nothing else does."""
    text, _ = conv_stack
    assert sc.KERNEL_NAMES[key] == name
    assert sorted(sc.KERNEL_NAMES.values()) == ["short_conv_bwd",
                                                "short_conv_fwd"]
    assert re.search(r"conv/[^\n\"]*" + name + r"[/\")]", text), name
    calls = re.findall(r"(short_conv_\w+)/pallas_call\"", text)
    # two runs of conv layers, each: the forward, the rematerialised
    # forward's is the same call site traced again, and one backward
    assert set(calls) == {"short_conv_fwd", "short_conv_bwd"}, calls


HC_KERNELS = [("pre_fwd", "mhc_pre_fwd"), ("post_fwd", "mhc_post_fwd"),
              ("post_bwd", "mhc_post_bwd"), ("pre_bwd", "mhc_pre_bwd")]


@pytest.fixture(scope="module")
def hc_texts():
    """One sublayer's forward + backward lowered at a shape the tile takes
    (4 streams of d 128 over 256 tokens) and at one it cannot (d 64), with
    the events each trace left."""
    from ray_tpu.perf.recorder import get_recorder

    kw = dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0), rms_eps=1e-6)

    def loss(x, p):
        out, _ = hc.hc_mix(x, p, lambda z: (2.0 * z, None), **kw)
        return sum(jnp.sum(jnp.square(o.astype(jnp.float32))) for o in out)

    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    out = {}
    try:
        for d in (128, 64):
            x = tuple(jnp.zeros((2, 128, d), jnp.bfloat16) for _ in range(4))
            p = {k: jnp.zeros(v) for k, v in hc.hc_param_shapes(4, d).items()}
            t0 = time.time()
            text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
                x, p).as_text(debug_info=True)
            out[d] = (text, [e for e in rec.snapshot(clear=False)
                             if e["kind"] == "rtpu.ops.hyper_connection"
                             and e["ts"] >= t0])
    finally:
        rec.enabled = was
    return out


@pytest.mark.parametrize("key,name", HC_KERNELS)
def test_hyper_connection_kernel_names_are_pinned(hc_texts, key, name):
    """ISSUE 47: a device trace and ``scope_ops.py`` show the mixings'
    four kernels by these, all under the scope ``mhc``, forward and
    backward."""
    assert hc.KERNEL_NAMES[key] == name
    assert len(set(hc.KERNEL_NAMES.values())) == len(HC_KERNELS) == len(
        hc.KERNEL_NAMES)
    others = set(fa.KERNEL_NAMES.values()) | set(
        fa.LATENT_KERNEL_NAMES.values()) | set(el.KERNEL_NAMES.values()) \
        | set(ssd.KERNEL_NAMES.values()) | set(sel.KERNEL_NAMES.values())
    assert not set(hc.KERNEL_NAMES.values()) & others
    text, _ = hc_texts[128]
    pattern = r"[/\"(]" + name + r"[/\")]"
    assert re.search(pattern, text)
    locs = [n for n in re.findall(r'loc\("([^"]+)"', text)
            if re.search(r"(^|/)" + name + r"($|/)", n)]
    assert locs and all(re.search(r"(^|[/(])mhc[/)]", n) for n in locs), locs
    # a call whose d does not tile the lanes holds no kernel
    assert not re.search(pattern, hc_texts[64][0])


@pytest.mark.parametrize("d,route", [(128, "kernel"), (64, "plain")])
def test_the_hyper_connections_route_leaves_its_event(hc_texts, d, route):
    """ISSUE 47: one ``rtpu.ops.hyper_connection`` event a traced
    sublayer, kind ``route``."""
    _, events = hc_texts[d]
    assert len(events) == 1 and events[0]["label"] == "route"
    assert events[0]["data"] == {"route": route, "streams": 4, "d": d,
                                 "tokens": 256, "tile": hc.TOKEN_TILE}


# what the call shows beside q [1, 128, 2, 64]: (v's heads, v's head size,
# window). One block of S: a call with one head size and no window would
# take the single-block kernels.
STREAMED_WHATEVER_S = {"windowed": (2, 64, 48), "paired": (1, 128, None),
                       "paired-windowed": (1, 128, 48)}


@pytest.fixture(scope="module")
def streamed_texts():
    q = jnp.zeros((1, 128, 2, 64), jnp.bfloat16)
    texts = {}
    for call, (hv, dv, window) in STREAMED_WHATEVER_S.items():
        v = jnp.zeros((1, 128, hv, dv), jnp.bfloat16)
        texts[call] = jax.jit(jax.grad(
            lambda q, k, v, window=window: fa.flash_attention(
                q, k, v, window=window).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))).lower(q, q, v).as_text(debug_info=True)
    return texts


@pytest.mark.parametrize("call", sorted(STREAMED_WHATEVER_S))
@pytest.mark.parametrize("key,name", [("fwd", "flash_fwd"),
                                      ("bwd_dq", "flash_bwd_dq"),
                                      ("bwd_dkv", "flash_bwd_dkv")])
def test_a_windowed_call_takes_the_streamed_kernels_by_name(streamed_texts,
                                                            key, name, call):
    """ISSUE 43: a call with a window runs the streamed one-part kernels
    under the names they have, whatever S (here one block), so that the
    readers that find them by name find a window layer's time too.
    ISSUE 44: so does a PAIRED call (two score heads of 64 against one
    value of 128), with and without a window: its kernels are these three
    and no other (``diff_attention_roofline`` finds their time by these
    names; ``tests/test_chip_compile.py`` reads the compiled program's
    custom calls)."""
    assert fa.KERNEL_NAMES[key] == name
    text = streamed_texts[call]
    assert re.search(r"[/\"(]" + name + r"[/\")]", text)
    assert "flash_fwd_single" not in text and "flash_bwd_fused" not in text


MODELS = {
    "sambay": lambda: SambaY(SambaYConfig.tiny()),
    "deepseek_v3": lambda: DeepseekV3(DeepseekV3Config.tiny(experts_held=4)),
    "deepseek_v3_hc": lambda: DeepseekV3(DeepseekV3Config.tiny(
        experts_held=4, hc_mult=4, q_lora_rank=16, rope_factor=64.0,
        rope_original_max=32, rope_mscale_all_dim=1.0)),
    "gpt": lambda: GPT(GPTConfig.tiny()),
    "granite_hybrid": lambda: GraniteHybrid(GraniteHybridConfig.tiny()),
    "kimi_linear": lambda: KimiLinear(KimiLinearConfig.tiny(experts_held=4)),
    "qwen3_next": lambda: Qwen3Next(Qwen3NextConfig.tiny(experts_held=4)),
    "nemotron_h": lambda: NemotronH(NemotronHConfig.tiny(
        experts_held=4, mamba_groups_held=1, heads_held=2)),
    "keye_vl2": lambda: KeyeVL2(KeyeVL2Config.tiny(experts_held=4)),
    "lfm2_moe": lambda: Lfm2Moe(Lfm2MoeConfig.tiny(experts_held=4)),
    "gpt-unrolled": lambda: GPT(GPTConfig.tiny(scan_layers=False)),
    "llama": lambda: Llama(LlamaConfig.tiny()),
}


@pytest.fixture(scope="module")
def lowered_losses():
    from ray_tpu.perf.recorder import get_recorder

    out = {}
    toks = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    try:
        for name, make in MODELS.items():
            m = make()
            p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
            mark = time.time()
            text = jax.jit(jax.value_and_grad(m.loss)).lower(
                p, toks, toks).as_text(debug_info=True)
            out[name] = set(re.findall(r'loc\("([^"]+)"', text))
            # ISSUE 55: what the trace left of the convolution's gradient
            out["rtpu.ops.conv", name] = [
                (e["label"], e["data"]) for e in rec.snapshot()
                if e["kind"] == "rtpu.ops.conv" and e["ts"] >= mark]
    finally:
        rec.enabled = was
    return out


@pytest.mark.parametrize("scope,model", [
    (s, m) for m in sorted(MODELS)
    # ISSUE 52: a Qwen3-Next shaped model has no dense MLP, so no ``mlp``
    for s in ("embed", "attn", "lm_head", "loss")
    # ISSUE 56: nor has a Nemotron-H shaped one
    + (("mlp",) if m not in ("qwen3_next", "nemotron_h", "keye_vl2")
       else ())
    + (("router", "experts", "shared_expert")
       if m.startswith("deepseek_v3")
       or m in ("kimi_linear", "qwen3_next", "nemotron_h") else ())
    # ISSUE 59: an expert layer WITHOUT a shared expert
    + (("router", "experts") if m in ("keye_vl2", "lfm2_moe") else ())
    # ISSUE 56: both projections of the experts' latent, under one name
    + (("latent_proj",) if m == "nemotron_h" else ())
    # ISSUE 45: everything ops/hyper_connection.py does, under one name
    + (("mhc",) if m == "deepseek_v3_hc" else ())
    # ISSUE 49: a KDA layer's three, the names the other scans' readers read
    + (("mixer", "conv", "scan")
       if m in ("granite_hybrid", "sambay", "kimi_linear", "qwen3_next",
                "nemotron_h") else ())
    # ISSUE 64: a conv operator's projections and its gated convolution,
    # under granite_hybrid's names; no scan follows them
    + (("mixer", "conv") if m == "lfm2_moe" else ())
    + (("gmu", "cross_attn") if m == "sambay" else ())])
def test_a_lowered_loss_carries_the_models_scopes(lowered_losses, model,
                                                  scope):
    names = lowered_losses[model]
    # forward and backward: the scope survives jvp and transpose
    fwd = [n for n in names if re.search(r"(^|/)jvp\(" + scope + r"\)/", n)
           or re.search(r"(^|/)" + scope + "/", n)]
    bwd = [n for n in names
           if re.search(r"transpose\(jvp\(" + scope + r"\)\)", n)
           or ("transpose" in n or "checkpoint" in n)
           and re.search(r"(^|/)" + scope + "/", n)]
    assert fwd, (model, scope)
    assert bwd, (model, scope)


@pytest.mark.parametrize("model,calls,data", [
    # three runs of KDA layers, each with q's, k's and v's convolution
    ("kimi_linear", 9, {"tokens": 256, "channels": 256, "taps": 4,
                        "bias": False}),
    # one run of Gated DeltaNet layers, one convolution over q | k | v
    ("qwen3_next", 1, {"tokens": 256, "channels": 1024, "taps": 4,
                       "bias": False}),
    # two runs of Mamba-2 layers, one convolution over x | B | C
    ("granite_hybrid", 2, {"tokens": 256, "channels": 512, "taps": 4,
                           "bias": True}),
    # one scanned run of Mamba-2 layers over the ONE group held: x | B | C
    ("nemotron_h", 1, {"tokens": 256, "channels": 384, "taps": 4,
                       "bias": True}),
    ("sambay", 0, None)])
def test_the_hand_gradient_of_the_convolution_leaves_its_event(
        lowered_losses, model, calls, data):
    """ISSUE 55: ``rtpu.ops.conv`` (label ``hand_vjp``) at trace time, once
    a traced call of ``causal_conv1d_silu`` (a scanned run traces its layer
    once), says which steps hold the hand-written gradient: the three
    models whose convolution feeds a scan kernel. ``sambay``'s feeds a
    matrix product and keeps autodiff's: its record holds none."""
    assert lowered_losses["rtpu.ops.conv", model] == [
        ("hand_vjp", data)] * calls


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_the_paged_entry_points_carry_the_same_scopes(family):
    m = GPT(GPTConfig.tiny()) if family == "gpt" else Llama(
        LlamaConfig.tiny())
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: m.init_paged_cache(16, 4))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    texts = {
        "decode": jax.jit(m.paged_decode_step).lower(
            p, cache, i32(2), i32(2), i32(2, 8),
            jax.ShapeDtypeStruct((2,), jnp.bool_)),
        "prefill": jax.jit(m.paged_prefill).lower(
            p, cache, i32(1, 8), i32(), i32(8)),
        "extend": jax.jit(m.paged_prefill_extend).lower(
            p, cache, i32(1, 8), i32(), i32(), i32(8)),
    }
    for which, lowered in texts.items():
        names = set(re.findall(r'loc\("([^"]+)"',
                               lowered.as_text(debug_info=True)))
        for scope in ("embed", "attn", "attn/kv_write", "attn/paged_attn",
                      "mlp", "lm_head"):
            assert any(re.search(r"(^|/)" + scope + r"(/|$)", n)
                       for n in names), (family, which, scope)
        assert not any("/loss/" in n for n in names)


def test_scopes_are_metadata_the_lowered_program_is_the_same():
    """``jax.named_scope`` changes no operation: a loss lowered with the
    scopes and one lowered with them stripped differ in locations only."""
    import contextlib
    from unittest import mock

    m = GPT(GPTConfig.tiny())
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 128), jnp.int32)

    def text():
        return jax.jit(jax.value_and_grad(m.loss)).lower(
            p, toks, toks).as_text()

    with_scopes = text()
    with mock.patch.object(jax, "named_scope",
                           lambda name: contextlib.nullcontext()):
        without = text()
    assert with_scopes == without
    assert np.all([s not in with_scopes for s in ("loc(", "attn/")])


@pytest.mark.parametrize("phase,kind", [
    ("jaxpr_trace_duration", "rtpu.jax.trace"),
    ("jaxpr_to_mlir_module_duration", "rtpu.jax.lower"),
    ("backend_compile_duration", "rtpu.jax.compile")])
def test_build_phase_span_kinds_are_pinned(phase, kind):
    """ISSUE 38: ``step_trace_lower_s``, ``step_compile_s``,
    ``other_programs_s`` and ``setup_unattributed_s`` find the chip
    worker's build phases by these kinds, and a program by the name a
    device trace gives it."""
    from ray_tpu.perf import get_recorder, jaxbuild

    assert jaxbuild._PHASES["/jax/core/compile/" + phase] == kind
    jaxbuild.install_jax_spans()

    def pinned_names_fn(x):
        return x + 1

    lowered = jax.jit(pinned_names_fn).lower(jnp.zeros(3))
    label = [ev["label"] for ev in get_recorder().spans("rtpu.jax.")
             if "pinned_names_fn" in ev["label"]
             and ev["kind"] != "rtpu.jax.trace"][-1]
    # the label of a lowered or compiled module IS the module's name
    assert re.search(r"module @" + label + r"\b", lowered.as_text())


def test_a_replicas_start_is_one_pinned_span():
    """ISSUE 38: ``rtpu.llm.start`` around ``LLMEngine``'s constructor,
    labelled with the engine; what the constructor built is its child."""
    from ray_tpu.perf import get_recorder

    m, params = build_model("gpt-tiny")
    rec = get_recorder()
    t0 = time.time()
    # a pool of a size no other test makes: its programs are built here
    eng = LLMEngine(m, params, EngineConfig(
        block_size=4, num_blocks=37, max_batch=4, max_blocks_per_seq=8,
        prefill_buckets=(8,)), name="names-start")
    start = rec.spans("rtpu.llm.start", since=t0)
    assert [ev["label"] for ev in start] == [eng.name]
    assert any(ev[1] == "rtpu.llm.start" and ev[2] == eng.name
               for ev in rec._pinned)
    inside = [ev for ev in rec.spans("rtpu.jax.", since=t0)
              if ev["parent"] == "rtpu.llm.start"
              and ev["ts"] + ev["dur"]
              <= start[0]["ts"] + start[0]["dur"] + 1e-3]
    assert "rtpu.jax.compile" in {ev["kind"] for ev in inside}


# -- ISSUE 69: a MiniCPM-SALA shaped stack -----------------------------------

la = importlib.import_module("ray_tpu.ops.lightning_attention")


@pytest.fixture(scope="module")
def sala_stack():
    """A MiniCPM-SALA shaped loss lowered (forward and backward) at S 256,
    longer than the preset's ``dense_len`` 64 -> (text, events)."""
    from ray_tpu.models import MiniCPMSALA, MiniCPMSALAConfig
    from ray_tpu.perf.recorder import get_recorder

    m = MiniCPMSALA(MiniCPMSALAConfig.tiny(heads_held=2, head_offset=2,
                                           ff_held=64, ff_offset=64))
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    try:
        text = jax.jit(jax.grad(m.loss)).lower(p, toks, toks).as_text(
            debug_info=True)
        events = rec.snapshot()
    finally:
        rec.enabled = was
    return text, events


def test_a_share_of_a_minicpm_sala_stack_leaves_its_events(sala_stack):
    """ISSUE 69: what a MiniCPM-SALA shaped loss leaves at trace time.
    ``rtpu.ops.lightning.path``: the kernel route with as many ``groups``
    as ``heads``, heads of 128 on a ``state`` of 128, ``decay``
    ``constant``, the ``chunk``; ``rtpu.ops.sparse_attention`` /
    ``selected`` with the second selector's facts: ``select_by`` ``block``,
    the ``block``, the ``blocks`` a query takes, ``init_blocks``,
    ``local_blocks``, the ``pool``, the kernel pair's blocks as the length
    chose them, what is ``saved`` and the selected beside the causal pairs;
    ``rtpu.models.stack.runs``: the attention layer with Keye's keep-set,
    then 3 scanned Lightning layers that keep their inputs alone, and the
    shares held."""
    _, events = sala_stack
    last = lambda kind: [e for e in events if e["kind"] == kind][-1]  # noqa: E731
    assert last("rtpu.ops.lightning.path")["data"] == {
        "route": "kernel", "chunk": 128, "heads": 2, "groups": 2,
        "head_dim": 128, "state": 128, "decay": "constant", "chunks": 2}
    sel = last("rtpu.ops.sparse_attention")
    assert sel["label"] == "selected" and sel["data"] == {
        "seq": 256, "select_by": "block", "block": 16, "blocks": 6,
        "init_blocks": 1, "local_blocks": 2, "pool": [8, 4],
        "dense_len": 64, "heads": 2, "kv_heads": 1, "head_dim": 128,
        "route": "masked_flash", "kernel_blocks": [256, 256],
        "backward": "fused", "bwd_products": 5,
        "saved": "block_mask_int8", "q_chunk": 256,
        "selected_pairs": sa.block_selected_pairs(256, 16, 6),
        "causal_pairs": 256 * 257 // 2}
    # 16 own blocks' causal halves; 0 .. 4 earlier blocks for the first five
    # blocks of queries, 5 for the other eleven
    assert sel["data"]["selected_pairs"] == 16 * (16 * 17 // 2) \
        + 16 * 16 * (0 + 1 + 2 + 3 + 4 + 5 * 11)
    runs = [e for e in events if e["kind"] == "rtpu.models.stack.runs"
            and e["label"] == "minicpm_sala"][-1]
    assert runs["data"]["runs"] == [["attn", 1], ["lightning", 3]]
    assert runs["data"]["kept"] == [["sparse_mask", "sparse_out",
                                     "sparse_lse", "flash_out",
                                     "flash_lse"], []]
    assert (runs["data"]["heads"], runs["data"]["kv_heads"],
            runs["data"]["head_offset"], runs["data"]["ff"],
            runs["data"]["ff_offset"]) == ([2, 4], [1, 2], 2, [64, 128], 64)


def _in_scope(names, scope):
    """The operation names that stand under ``scope``: ``scope/`` inside a
    scanned body, ``jvp(scope)`` where the layer is a run of one."""
    return [n for n in names
            if re.search(r"(^|/|\()" + scope + r"(/|\))", n)]


@pytest.mark.parametrize("key,name", [("fwd", "lightning_chunk_fwd"),
                                      ("bwd", "lightning_chunk_bwd")])
def test_lightning_kernel_names_are_pinned(sala_stack, key, name):
    """ISSUE 69: ``lightning_scan_roofline`` finds its kernels by these;
    they stand under the scope ``scan``, the masked pair (whose names
    ``block_sparse_attention_roofline`` reads, unchanged) under ``attn``,
    and no state-space kernel of ``ssd_scan`` is in the program."""
    text, _ = sala_stack
    names = set(re.findall(r'loc\("([^"]+)"', text))
    assert la.KERNEL_NAMES[key] == name
    assert sorted(la.KERNEL_NAMES.values()) == ["lightning_chunk_bwd",
                                                "lightning_chunk_fwd"]
    assert [n for n in _in_scope(names, "scan") if "/" + name + "/" in n]
    for other in ssd.KERNEL_NAMES.values():
        assert other not in text
    for masked in sa.KERNEL_NAMES.values():
        assert [n for n in _in_scope(names, "attn")
                if "/" + masked + "/" in n], masked
    assert "sparse_attn_bwd_dq" not in text


@pytest.mark.parametrize("scope", ["indexer", "select"])
def test_the_block_selection_has_scopes_and_no_backward(sala_stack, scope):
    """ISSUE 69: ``train_indexer_ms`` and ``train_select_ms`` read these
    scopes in this family too (the innermost on an operation's name stack,
    under ``attn``). No gradient passes the selection: nothing of it stands
    under a transpose but what a rematerialised layer makes AGAIN there
    (the bytes a pair, expanded from the kept blocks), and the pooled
    scores are not among that."""
    text, _ = sala_stack
    names = set(re.findall(r'loc\("([^"]+)"', text))
    mine = _in_scope(names, scope)
    assert mine, scope
    assert [n for n in mine if _in_scope([n], "attn")]
    again = [n for n in mine if "transpose(" in n]
    assert all("rematted_computation" in n for n in again), scope
    if scope == "indexer":
        assert not again


def test_the_chunked_head_keeps_its_scopes(sala_stack):
    """ISSUE 69: the head and the loss walked in token chunks
    (``ops.chunked_head_nll``, which ``models/gpt.py`` reaches too) stand
    under ``lm_head`` and ``loss``, which ``train_head_loss_ms`` reads."""
    text, _ = sala_stack
    names = set(re.findall(r'loc\("([^"]+)"', text))
    for scope in ("lm_head", "loss", "embed", "mixer", "scan", "mlp", "attn"):
        assert _in_scope(names, scope), scope
