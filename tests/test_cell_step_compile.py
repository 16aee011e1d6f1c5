"""A benchmark cell's WHOLE train step compiles for the described TPU v5e
and keeps its room: the bytes of its temporaries, what the compiler's own
rematerialisation made again, its matmul operations and its kernels by
name and count. A case is a compile of 50 to 120 s, so the file is ``slow``
(``pytest.ini``) and tier-1 leaves it out; CI runs it in a step of its own.
What it guards moves only with a ``perf_opt`` PR on that cell, whose builder
runs the cell's case (``-m slow -k <cell>``); the kernels by name and every
cell's scopes stay in tier-1 (``tests/test_tracing_names.py`` and the
``*_at_the_benchmark_cells_shape`` cases of ``test_chip_compile.py``). A
``model_config`` PR adds its cell's case HERE.
"""
import os
import re

import pytest

from _chip_compile import (compiled_kernels, fits, hlo_tool,  # noqa: F401
                           one_chip)

pytestmark = pytest.mark.slow


@pytest.fixture
def tool(monkeypatch):
    """``scripts/train_step_hlo.py``, with the repo's root where its
    ``compile_step`` finds ``benchmark`` and ``ray_tpu``."""
    monkeypatch.syspath_prepend(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return hlo_tool()


def _kernel_count(text: str):
    """name -> how many ``tpu_custom_call``s of an optimised program are
    that kernel (``name`` or ``name.<n>``)."""
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    return lambda name: sum(
        1 for c in calls if re.match(r"\s*%?" + name + r"(\.\d+)?$", c))


def _unfused_under(text: str, scope: str):
    """The instructions of an optimised program under the model's scope
    ``scope`` (by their ``op_name``): of the entry and the loops' bodies,
    not those inside a fusion (they live in registers and VMEM)."""
    fused = set(re.findall(r" fusion\(.*?calls=%?([\w.\-]+)", text))
    inside = None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            inside = head.group(1)
        elif inside not in fused:
            op = re.search(r'op_name="([^"]*)"', line)
            if op and re.search(r"[/(]" + scope + r"[/)]", op.group(1)):
                yield line


def _experts_first_half_is_the_kernel_pair(text: str, runs: int) -> bool:
    """ISSUE 68: a run of expert layers (a loop's body, or a layer by
    itself) holds ``expert_hidden_fwd`` twice (the forward sweep and the
    rematerialised layer) and ``expert_hidden_bwd`` once, all under the scope
    ``experts``; of the grouped product what is left is ``e_down``'s (forward,
    at most again, and transposed for h's cotangent: 2 or 3 a run where the
    unfused first half made 8 or 9)."""
    count = _kernel_count(text)
    for line in text.splitlines():
        if re.match(r"\s*%?expert_hidden_(fwd|bwd)(\.\d+)? = ", line):
            if not re.search(r'op_name="[^"]*[/(]experts[/)]', line):
                return False
    return (count("expert_hidden_fwd") == 2 * runs
            and count("expert_hidden_bwd") == runs
            and 2 * runs <= count("grouped_matmul") <= 3 * runs)


def _buffers_under(text: str, shape: str, scope: str):
    """The instructions of an optimised program that PRODUCE an array of
    ``shape`` (``f32[2,8192,4096]``) under the model's scope ``scope``."""
    return [line.split(" = ")[0].strip()
            for line in _unfused_under(text, scope)
            if re.match(r"\s*(?:ROOT )?%?\S+ = " + re.escape(shape) + r"[{ ]",
                        line)]


def _conv_fusions_write_one_array_each(text: str, shape: str) -> bool:
    """ISSUE 55: inside the rematerialised, scanned layers too, every fusion
    under the scope ``conv`` writes at most ONE array of ``shape`` (the
    forward y; the backward dpre beside the sums for dw and db; dx).
    Autodiff's backward had fusions there with two (the rematerialised
    forward wrote its pre-activation for the backward to read), three and
    four (one shifted product a tap)."""
    written = [line.split(" fusion(")[0].partition(" = ")[2].count(shape)
               for line in _unfused_under(text, "conv") if " fusion(" in line]
    # forward, rematerialised forward, dpre, dx
    return max(written) == 1 and sum(written) >= 4


def test_granite4h_train_step_keeps_its_room(one_chip, compiled_kernels,
                                             tool):
    """ISSUE 37: granite4h_train_s4096's own train step (the harness's
    ``make_train_step``, the cell's configuration, optimizer, batch 2 of
    4096, parameters and optimizer state donated) for the described v5e,
    the runs after the first keeping the gated MLP's two input products:
    8 652 767 744 bytes of temporaries beside 9.27 GB of arguments
    (8 635 408 896 since ISSUE 55's hand-written gradient of the
    convolution; 8 591 307 776 with nothing kept: what later runs keep is
    freed before the peak, which is in the first run's backward), and the
    compiler rematerialises NOTHING on its own. It does as soon as the first
    run keeps a product too (``.remat`` instructions: the head's logits made
    again, then the mixers' products), and with every layer keeping both
    the program holds more matmul operations than with nothing kept. A
    change that eats the room fails here, not as a slower step on the
    chip."""
    compiled = tool.compile_step("granite4h_train_s4096", one_chip)
    # 772 M parameters and two adam moments in float32, donated
    assert 9.2e9 < fits(compiled) < 9.3e9
    assert compiled.memory_analysis().temp_size_in_bytes < 9.0e9
    text = compiled.as_text()
    assert "s32[2,4096]" in text            # the cell's batch, not another
    assert tool.compiler_remat(text) == 0
    # 47.42 T with nothing kept, 44.67 T as kept here, 49.80 T with every
    # layer keeping both (scripts/train_step_hlo.py --census)
    census = tool.matmul_census(text)
    assert sum(census.values()) < 44.8e12
    assert census["mlp"] < 27.6e12          # 30.24 T with nothing kept
    # the kept stacks of the runs of 1 and 4 are in the program, written
    # by the product's own fusion; the run of 5 has none
    assert "bf16[4,2,4096,8192]" in text
    assert "bf16[5,2,4096,8192]" not in text
    assert _conv_fusions_write_one_array_each(text, "bf16[2,4096,4352]")


def test_phi4flash_train_step_keeps_its_room(one_chip, compiled_kernels,
                                             tool):
    """ISSUE 43: phi4flash_train_s8192's own train step (the harness's
    ``make_train_step``, the cell's configuration, optimizer, batch 1 of
    8192, parameters and optimizer state donated) for the described v5e:
    697 M parameters at 12 B as arguments (8.37 GB), 5.454 GB of
    temporaries with every layer keeping the kernels' outputs, the MLP's
    two products and the mixers' input projections (5.230 GB with nothing
    kept), the compiler rematerialising nothing on its own, 35.3 T matmul
    operations a step (42.0 T with nothing kept; ``scripts/
    train_step_hlo.py --census``), and each kernel in the program as often
    as the six layers need it: no forward kernel a second time."""
    compiled = tool.compile_step("phi4flash_train_s8192", one_chip)
    assert 8.3e9 < fits(compiled) < 8.45e9
    assert compiled.memory_analysis().temp_size_in_bytes < 5.6e9
    text = compiled.as_text()
    assert "s32[1,8192]" in text            # the cell's batch, not another
    assert tool.compiler_remat(text) == 0
    assert sum(tool.matmul_census(text).values()) < 35.5e12
    count = _kernel_count(text)
    # two Mamba-1 layers; a window, a full and a cross layer
    assert count("selscan_chunk_fwd") == count("selscan_chunk_bwd") == 2
    assert count("flash_fwd") == count("flash_bwd_dq") \
        == count("flash_bwd_dkv") == 3


def test_xing4_train_step_keeps_its_room(one_chip, compiled_kernels,
                                         tool):
    """ISSUE 45: xing4_train_s4096's own train step (the harness's
    ``make_train_step``, the cell's configuration, optimizer, batch 2 of
    4096, parameters and optimizer state donated) for the described v5e:
    759.5 M parameters at 12 B as arguments (9.11 GB), 10.43 GB of
    temporaries (they overlap the donated state) with a layer keeping its
    four streams, the latent kernels' output and row statistics and NOT q
    (``_REMAT_SAVE_BOTTLENECK``: with q kept too the compiler refused the
    step by 1.73 MB). The room is gone: the compiler makes instructions
    again on its own to fit (``scripts/train_step_hlo.py --census``: 17
    before ISSUE 47, mixed streams and the logits once; 9 and 10.58 GB of
    temporaries with the mixings as kernels; 3 and 9.80 GB since ISSUE 62,
    the expert layer's selects over its row buffer gone), which is what a
    change that needs more memory would turn into a refusal here and not on
    the chip.
    The latent kernels stand once a layer and direction, the mixings'
    backward kernels once a sublayer of the dense layer and of the scanned
    body (ISSUE 47), and no stream is laid out [tokens, 4, d] (4 rows
    padded to 16)."""
    compiled = tool.compile_step("xing4_train_s4096", one_chip)
    assert 9.05e9 < fits(compiled) < 9.2e9
    assert compiled.memory_analysis().temp_size_in_bytes < 10.6e9
    text = compiled.as_text()
    assert "s32[2,4096]" in text            # the cell's batch, not another
    assert tool.compiler_remat(text) <= 17
    count = _kernel_count(text)
    # the dense layer by itself and the scanned expert layers' one body
    assert count("flash_latent_fwd") == count("flash_latent_bwd_dkv") == 2
    assert count("mhc_post_bwd") == count("mhc_pre_bwd") == 4
    assert count("mhc_pre_fwd") >= 4 and count("mhc_post_fwd") >= 4
    assert not re.findall(r"\w+\[2,4096,4,3584\]", text)
    assert not re.findall(r"f32\[8192,4,4\]|f32\[2,4096,4,4\]", text)
    assert _experts_first_half_is_the_kernel_pair(text, runs=1)


# 48 s alone since the delta rule is a kernel pair (85 s with its loops of
# 128 turns x 4 layers x 3 passes); beside five other workers it can still
# pass the default 180 s
@pytest.mark.time_limit(480)
def test_kimilinear_train_step_keeps_its_room(one_chip, compiled_kernels,
                                              tool):
    """ISSUES 49, 50, 51: kimilinear_train_s8192's own train step (the
    harness's ``make_train_step``, the cell's configuration, optimizer,
    batch 2 of 8192, parameters and optimizer state donated) for the
    described v5e, the expert layer's kernels on their compiled path as on
    the chip (in interpret mode its row buffers are refused by 1.30 GB):
    602.4 M parameters at 12 B as arguments (7.23 GB), 7.55 GB of
    temporaries (they overlap the donated state; 7.95 GB while autodiff made
    the convolutions' backward with four full-size arrays each, ISSUE 55;
    8.76 GB while the plain code made the norms of q and k and wrote the
    gate g in float32, ISSUE 51) with a KDA layer keeping its input alone
    and the latent layer its kernels' output, row statistics and q; the
    compiler makes NO instruction again on its own (2 before ISSUE 51; 20
    before the delta rule's kernels freed the turns' stacked inputs; 20
    again with one KDA layer's o and chunk states kept, 45 with all four:
    why they are not). Under the scope ``scan`` no float32 [2, 8192, 4096]
    array is produced any more (the parent's step held 21 such producers
    there: the gate, its broadcast factor, the norms' squares, dg and its
    products): the kernels read what the convolutions and the gate
    projection made. The one latent layer's two kernels stand once each; the
    delta rule's forward kernel stands twice a run of KDA layers (the
    forward sweep and the rematerialised layer) and its backward once."""
    compiled = tool.compile_step("kimilinear_train_s8192", one_chip)
    assert 7.2e9 < fits(compiled) < 7.3e9
    assert compiled.memory_analysis().temp_size_in_bytes < 7.7e9
    text = compiled.as_text()
    assert "s32[2,8192]" in text            # the cell's batch, not another
    assert tool.compiler_remat(text) <= 6
    assert _buffers_under(text, "f32[2,8192,4096]", "scan") == []
    assert _buffers_under(text, "f32[2,8192,4096]", "mixer")  # it can see
    count = _kernel_count(text)
    assert count("flash_latent_fwd") == count("flash_latent_bwd_dkv") == 1
    assert count("kda_chunk_fwd") == 6 and count("kda_chunk_bwd") == 3
    for line in text.splitlines():          # all nine under the scope
        if re.match(r"\s*%?kda_chunk_(fwd|bwd)(\.\d+)? = ", line):
            assert re.search(r'op_name="[^"]*[/(]scan[/)]', line), line[:200]
    # ISSUE 68: three runs of expert layers (7.68 GB of temporaries)
    assert _experts_first_half_is_the_kernel_pair(text, runs=3)
    assert count("grouped_matmul_dw") >= 9
    assert _conv_fusions_write_one_array_each(text, "bf16[2,8192,4096]")


# 70 s alone (the compile of four layers' kernels and the sort of 163 840
# pairs a layer); beside five other workers it can pass the default 180 s
@pytest.mark.time_limit(480)
def test_qwen3next_train_step_keeps_its_room(one_chip, compiled_kernels,
                                             tool):
    """ISSUE 52: qwen3next_train_s8192's own train step (the harness's
    ``make_train_step``, the cell's configuration, optimizer, batch 2 of
    8192, parameters and optimizer state donated) for the described v5e, the
    expert layer's kernels on their compiled path as on the chip: 626.0 M
    parameters at 12 B as arguments (7.51 GB), 9.50 GB of temporaries since
    ISSUE 53 (9.77 while q and k were repeated to the value heads; they
    overlap the donated state) with a Gated DeltaNet layer keeping its input
    alone and the attention layer its kernels' output and row statistics
    (nothing kept in the attention layer reads 9.7677 against 9.7679 GB; a
    Gated DeltaNet layer keeping ``kda_out`` and ``kda_states`` is refused,
    "Used 16.80G of 15.75G hbm"); the compiler made 3 instructions again on
    its own (4 until ISSUE 55: the convolution's forward a third time, for
    autodiff's backward). Since ISSUE 62 (the expert layer writes no zeros
    over its row buffer: three selects over [172 032, 2048] fewer a layer)
    it makes NONE again and holds 9.92 GB of temporaries: what it made again
    (2.47 T operations of ``mixer`` products, ``--census``) it now keeps.
    Under the scope ``scan`` no float32 [2, 8192, 4096] array is produced:
    the kernels make the norms and the gate from
    what the convolution and ``W_ba`` left. The one attention layer's two
    one-part flash kernels stand once each; the delta rule's forward kernel
    (ISSUE 53: ``gdn_chunk_fwd``, the body for one decay a head; KDA's is
    not in the program) twice in the scanned run's loops (the forward sweep
    and the rematerialised layer) and its backward once."""
    compiled = tool.compile_step("qwen3next_train_s8192", one_chip)
    assert 7.5e9 < fits(compiled) < 7.6e9
    # ISSUE 68: 10.22 GB with the experts' first half a kernel pair (the
    # backward kernel's five outputs and two inputs sized by the buffer are
    # live together where the unfused passes' were not)
    assert compiled.memory_analysis().temp_size_in_bytes < 10.3e9
    text = compiled.as_text()
    assert "s32[2,8192]" in text            # the cell's batch, not another
    assert tool.compiler_remat(text) <= 8
    assert _buffers_under(text, "f32[2,8192,4096]", "scan") == []
    count = _kernel_count(text)
    assert count("flash_fwd") == 1          # kept: not run again
    assert count("flash_bwd_dq") + count("flash_bwd_fused") == 1
    assert count("gdn_chunk_fwd") == 2 and count("gdn_chunk_bwd") == 1
    assert count("kda_chunk_fwd") == 0 and count("kda_chunk_bwd") == 0
    for line in text.splitlines():          # all three under the scope
        if re.match(r"\s*%?gdn_chunk_(fwd|bwd)(\.\d+)? = ", line):
            assert re.search(r'op_name="[^"]*[/(]scan[/)]', line), line[:200]
    assert _experts_first_half_is_the_kernel_pair(text, runs=2)
    assert count("grouped_matmul_dw") >= 6
    assert _conv_fusions_write_one_array_each(text, "bf16[2,8192,8192]")


# 60 s alone; beside five other workers it can pass the default 180 s
@pytest.mark.time_limit(480)
def test_lfm2moe_train_step_keeps_its_room(one_chip, compiled_kernels, tool):
    """ISSUE 64: lfm2moe_train_s8192's own train step (the harness's
    ``make_train_step``, the cell's configuration, optimizer, batch 2 of
    8192, parameters and optimizer state donated; the model's ``loss``
    holds the routers' balancing term) for the described v5e: 507.8 M
    parameters at 12 B as arguments (6.09 GB) and 7.2 GB of temporaries
    (they overlap the donated state), the compiler making nothing again on
    its own. The convolution's forward kernel stands four times (the dense
    layer and the scanned run of three, each in the forward sweep and in
    the rematerialised layer: a conv layer keeps its input alone) and its
    backward twice, all six under the scope ``conv``, where nothing else
    stands that makes an array of the batch: the kernels' operand is W_in's
    one output. The ONE attention layer's flash kernels stand once each
    (its output and row statistics are kept)."""
    compiled = tool.compile_step("lfm2moe_train_s8192", one_chip)
    assert 6.0e9 < fits(compiled) < 6.2e9
    assert compiled.memory_analysis().temp_size_in_bytes < 7.5e9
    text = compiled.as_text()
    assert "s32[2,8192]" in text            # the cell's batch, not another
    assert tool.compiler_remat(text) <= 4
    count = _kernel_count(text)
    assert count("flash_fwd") == 1          # kept: not run again
    assert count("flash_bwd_dq") == 1 and count("flash_bwd_dkv") == 1
    assert count("short_conv_fwd") == 4 and count("short_conv_bwd") == 2
    for line in text.splitlines():          # all six under the scope
        if re.match(r"\s*%?short_conv_(fwd|bwd)(\.\d+)? = ", line):
            assert re.search(r'op_name="[^"]*[/(]conv[/)]', line), line[:200]
    made = [line.split(" = ")[0].strip() for line in _unfused_under(
        text, "conv") if re.search(r" = \(?\w+\[2,8192,", line)
        and " get-tuple-element(" not in line]
    assert all(re.match(r"%?short_conv_(fwd|bwd)(\.\d+)?$", m)
               for m in made), made
    assert _experts_first_half_is_the_kernel_pair(text, runs=2)
    assert count("grouped_matmul_dw") >= 6


# 50 to 80 s each alone
@pytest.mark.time_limit(480)
@pytest.mark.parametrize("cell,arguments,temporaries,wide", [
    ("nemotron3super_train_s8192", (8.3e9, 8.6e9), 9.9e9, "133120,2688"),
    ("keyevl2_train_s16384", (0.0, 16e9), 13.2e9, "135168,768")])
def test_a_claimed_cells_train_step_keeps_its_room(
        one_chip, compiled_kernels, tool, cell, arguments, temporaries, wide):
    """ISSUE 68: the two claimed cells' own train steps for the described
    v5e with the experts' first half a kernel pair: 9.76 GB of temporaries
    in ``nemotron3super_train_s8192`` (10.53 GB unfused, 8.41 GB of
    arguments) and 13.10 GB in ``keyevl2_train_s16384`` (13.09 unfused), the
    compiler making nothing again on its own, ONE run of expert layers each,
    and under the scope ``experts`` nothing but a kernel makes an array of
    [buffer rows, F]."""
    compiled = tool.compile_step(cell, one_chip)
    assert arguments[0] < fits(compiled) < arguments[1]
    assert compiled.memory_analysis().temp_size_in_bytes < temporaries
    text = compiled.as_text()
    assert tool.compiler_remat(text) == 0
    assert _experts_first_half_is_the_kernel_pair(text, runs=1)
    made = [line.split(" = ")[0].strip()
            for line in _unfused_under(text, "experts")
            if re.search(r" = \(?\w+\[%s\]" % wide, line)
            and " get-tuple-element(" not in line]
    assert made and all(re.match(
        r"%?(expert_hidden_(fwd|bwd)|grouped_matmul)(\.\d+)?$", m)
        for m in made), made


# 25 s alone; beside five other workers it can pass the default 180 s
@pytest.mark.time_limit(480)
def test_olmohybrid_train_step_keeps_its_room(one_chip, compiled_kernels,
                                              tool):
    """ISSUE 67: olmohybrid_train_s8192's own train step (the harness's
    ``make_train_step``, the cell's configuration, optimizer, batch 1 of
    8192, parameters and optimizer state donated) for the described v5e:
    766.2 M parameters at 12 B as arguments (9.20 GB) and 7.81 GB of
    temporaries (they overlap the donated state; 9.92 at batch 2, accepted
    too) with a Gated DeltaNet layer keeping its input alone and the
    attention layer its kernels' output and row statistics. The delta rule
    runs the KERNEL route on padded lanes: Gated DeltaNet's own pair
    (``gdn_chunk_fwd`` twice in the scanned run's loops, the forward sweep
    and the rematerialised layer, ``gdn_chunk_bwd`` once; KDA's not in the
    program), all three under the scope ``scan``, q and k read as [1, 8192,
    15 x 128] and v as [1, 8192, 30 x 128]; the ONE attention layer's flash
    kernels stand once each."""
    compiled = tool.compile_step("olmohybrid_train_s8192", one_chip)
    assert 9.1e9 < fits(compiled) < 9.3e9
    assert compiled.memory_analysis().temp_size_in_bytes < 8.2e9
    text = compiled.as_text()
    assert "s32[1,8192]" in text            # the cell's batch, not another
    assert tool.compiler_remat(text) <= 4
    count = _kernel_count(text)
    assert count("flash_fwd") == 1          # kept: not run again
    assert count("flash_bwd_dq") + count("flash_bwd_fused") == 1
    assert count("gdn_chunk_fwd") == 2 and count("gdn_chunk_bwd") == 1
    assert count("kda_chunk_fwd") == 0 and count("kda_chunk_bwd") == 0
    for line in text.splitlines():          # all three under the scope
        if re.match(r"\s*%?gdn_chunk_(fwd|bwd)(\.\d+)? = ", line):
            assert re.search(r'op_name="[^"]*[/(]scan[/)]', line), line[:200]
            assert "bf16[1,8192,1920]" in line and "bf16[1,8192,3840]" in line


# 45 s alone; beside five other workers it can pass the default 180 s
@pytest.mark.time_limit(480)
def test_minicpmsala_train_step_keeps_its_room(one_chip, compiled_kernels,
                                               tool):
    """ISSUE 69: minicpmsala_train_s32768's own train step (the harness's
    ``make_train_step``, the cell's configuration, optimizer, batch 1 of
    32 768, parameters and optimizer state donated) for the described v5e:
    630.2 M parameters at 12 B as arguments (7.56 GB) and 11.5 GB of
    temporaries (they overlap the donated state) with the head and loss in 8
    chunks of 4096 tokens, the attention layer keeping its input, the
    selection as one byte a (query, key block) and the masked kernels'
    output and row statistics, a Lightning layer its input alone. The
    recurrence runs ITS OWN kernel pair (``lightning_chunk_fwd`` twice in
    the scanned run's loops, the forward sweep and the rematerialised
    layer, ``lightning_chunk_bwd`` once; ``ssd_scan``'s pair is not in the
    program) under the scope ``scan`` on the merged [1, 32768, 16 x 128]
    arrays; the ONE attention layer's masked kernels stand once each (kept:
    not run again); no float array of 32768 x 32768, and never the float32
    logits of the whole row."""
    compiled = tool.compile_step("minicpmsala_train_s32768", one_chip)
    assert 7.5e9 < fits(compiled) < 7.65e9
    assert compiled.memory_analysis().temp_size_in_bytes < 12.0e9
    text = compiled.as_text()
    assert "s32[1,32768]" in text           # the cell's batch, not another
    count = _kernel_count(text)
    assert count("sparse_attn_fwd") == 1 and count("sparse_attn_bwd_dkv") == 1
    assert count("lightning_chunk_fwd") == 2
    assert count("lightning_chunk_bwd") == 1
    assert count("ssd_chunk_fwd") == 0 and count("ssd_chunk_bwd") == 0
    for line in text.splitlines():          # all three under the scope
        if re.match(r"\s*%?lightning_chunk_(fwd|bwd)(\.\d+)? = ", line):
            assert re.search(r'op_name="[^"]*[/(]scan[/)]', line), line[:200]
            assert "bf16[1,32768,2048]" in line
    assert "s8[1,1,32768,512]" in text      # the selection, a byte a block
    assert not re.findall(r"\b(?:f32|bf16)\[[\d,]*32768,32768\]", text)
    assert not re.findall(r"\bf32\[(?:1,)?32768,9216\]", text)
    assert re.search(r"f32\[4096,9216\]", text)     # a chunk's logits
