"""The main path's kernels and programs, compiled for a TPU v5e that is
described and not attached (on-chip-measurement guide, section 2.3).

Nothing runs here: a compile that passes says the chip's compiler takes
the program at its real widths — tiling, fast-memory use, device memory
— and that the Pallas kernel is in it (``tpu_custom_call``). It is not
a chip run and gives no results or times; ``chip_smoke.py`` is the run.

All of these live in ONE file and describe the topology inside a
module-scoped fixture: only one process may load the TPU library, so
nothing touches it at import, in a ``skipif``, in ``parametrize``
arguments or in conftest.py, and only the xdist worker that is handed
this file loads it.
"""
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    """Sharding on the first device of a described v5e 2x2 host, with
    jax's persistent compilation cache switched off around the module:
    an executable compiled for a described device is written to the
    cache but cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


@pytest.mark.parametrize("shape,dtype,causal", [
    ((8, 16, 1024, 64), jnp.bfloat16, True),    # GPT-style LM block
    ((4, 12, 384, 64), jnp.bfloat16, False),    # BERT fine-tune length
    ((16, 12, 512, 64), jnp.float32, False),    # BERT-base, chip_smoke.py
    ((2, 8, 4096, 128), jnp.bfloat16, True),    # long context, wide head
    ((4, 8, 512, 96), jnp.float32, False),      # head_dim padded 96 -> 128
    ((2, 12, 400, 64), jnp.float32, True),      # odd T: tail block masked
], ids=["lm1024-bf16-causal", "bert384-bf16", "bert512-f32",
        "long4096x128-bf16-causal", "pad-d96-f32", "odd-t400-f32-causal"])
def test_flash_attention_forward_and_backward_compile(one_chip, shape,
                                                      dtype, causal):
    from mxnet_tpu.ops.pallas_kernels import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=causal)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    qkv = _shapes(one_chip, *[(shape, dtype)] * 3)
    for fn in (fwd, jax.grad(loss, argnums=(0, 1, 2))):
        text = jax.jit(fn).lower(*qkv).compile().as_text()
        assert "tpu_custom_call" in text, \
            "the dense composition was compiled, not the Pallas kernel"
        # no T x T array outside the kernels: scores and probabilities
        # never reach HBM, forward or backward
        assert f",{shape[2]},{shape[2]}]" not in text
        if dtype == jnp.float32:
            # nor a whole-array rounding of an operand or a result
            assert "bf16[" not in text


@pytest.mark.parametrize("shape", [
    (64, 3, 7, 7), (256, 64, 1, 1), (512, 512, 3, 3), (1000, 2048),
], ids=["stem7x7", "bottleneck1x1", "conv3x3-512", "fc"])
def test_fused_mp_sgd_kernel_compiles(one_chip, shape):
    """opt.kernels' fused mixed-precision SGD + cast, at ResNet-50
    weight shapes with bf16 gradients."""
    from mxnet_tpu.opt.kernels import _mp_sgd_call
    grad, mom, w32 = _shapes(one_chip, (shape, jnp.bfloat16),
                             (shape, jnp.float32), (shape, jnp.float32))
    lr, wd, rescale = _shapes(one_chip, *[((), jnp.float32)] * 3)
    text = _mp_sgd_call.lower(
        grad, mom, w32, lr, wd, rescale, out_dtype="bfloat16",
        momentum=0.9, clip=None, interpret=False).compile().as_text()
    assert "tpu_custom_call" in text


def test_serve2_scan_decode_and_prefill_compile(one_chip):
    """PagedLM's chip-only formulation — scan paged attention over
    donated pools — at the serving widths of chip_smoke.py (depth, vocab
    and pool cut: they change the program's size, not its tiling)."""
    from mxnet_tpu.parallel.pipeline_lm import init_pipeline_lm
    from mxnet_tpu.serve2.decode import PagedLM
    params = init_pipeline_lm(0, vocab=1024, d_model=768, n_layers=2,
                              n_heads=12, d_head=64, d_ff=3072,
                              n_experts=1)
    lm = PagedLM(params, page_size=16, num_pages=64, max_pages_per_seq=8,
                 donate="on", attention="scan", decode_steps=4)

    def sds(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    i32 = jnp.int32
    bt, vec = _shapes(one_chip, ((8, 8), i32), ((8,), i32))
    decode = jax.jit(lm._decode_fn, donate_argnums=(1,)).lower(
        sds(lm.params), sds(lm.pools), bt, vec, vec, vec).compile()
    bt_row, length, tokens = _shapes(one_chip, ((8,), i32), ((), i32),
                                     ((128,), i32))
    prefill = jax.jit(lm._prefill_fn, donate_argnums=(1,)).lower(
        sds(lm.params), sds(lm.pools), bt_row, length, tokens).compile()
    pool_bytes = sum(int(p.nbytes) for p in lm.pools.values())
    for prog in (decode, prefill):
        # the donated pools are updated in place: the program's outputs
        # alias them instead of being allocated beside them
        assert prog.memory_analysis().alias_size_in_bytes >= pool_bytes


@pytest.mark.parametrize("heads,window", [(64, 512), (48, None)],
                         ids=["sliding-64-heads", "full-48-heads"])
def test_banded_attention_compiles_as_a_kernel(one_chip, heads, window):
    """The Laguna cell's two attention shapes (8192 tokens, 8 key/value
    heads of 128): the splash kernel, forward and backward, not the XLA
    composition."""
    from mxnet_tpu.ops.banded_attention import banded_attention

    def loss(q, k, v):
        return banded_attention(q, k, v, window=window, backend="splash") \
            .astype(jnp.float32).sum()

    q, k, v = _shapes(one_chip, ((1, heads, 8192, 128), jnp.bfloat16),
                      ((1, 8, 8192, 128), jnp.bfloat16),
                      ((1, 8, 8192, 128), jnp.bfloat16))
    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, v).compile() \
        .as_text()
    assert text.count("tpu_custom_call") >= 2


def test_a_sliding_layer_compiles_as_the_band_kernel_alone(one_chip):
    """The Laguna cell's window layer between its projections (rotary
    positions on q and k, attention over a window of 512, the per-head
    gate; 8192 tokens, 64 query heads over 8 key/value heads of 128,
    bfloat16), result and gradients: the band kernel forward and
    backward, which reads the heads where the projections leave them.
    Beside the operands and the kernels' results the program holds no
    array of q's size in any dtype: no transpose, no relayout copy, no
    pad, no scaled or float32 copy of q, o, do or dq."""
    import functools
    from mxnet_tpu.models import laguna
    t, heads, kv_heads, d = 8192, 64, 8, 128
    cos, sin, turn = laguna.rotary_tables(
        t, d, {"rope_type": "default", "rope_theta": 10000.0,
               "partial_rotary_factor": 0.5})
    block = functools.partial(
        laguna._gated_attention, cos=cos, sin=sin, turn=turn, heads=heads,
        kv_heads=kv_heads, head_dim=d, window=512, backend="band")

    def both(q, k, v, gate, ct):
        out, vjp = jax.vjp(block, q, k, v, gate)
        return (out,) + vjp(ct)

    bf16 = jnp.bfloat16
    args = _shapes(one_chip, ((1, t, heads * d), bf16),
                   ((1, t, kv_heads * d), bf16), ((1, t, kv_heads * d), bf16),
                   ((1, t, heads), bf16), ((1, t, heads * d), bf16))
    text = jax.jit(both).lower(*args).compile().as_text()
    for kernel in ("band_attention_fwd", "band_attention_bwd"):
        assert re.search(rf"{kernel}\S* = .*custom-call", text), kernel
    # what the entry computation holds is what lies in device memory
    # (a fused computation's own instructions never leave the chip's
    # fast memory)
    entry = text[text.index("ENTRY"):]
    big = []
    for m in re.finditer(r"%?([\w.\-]+) = (\w+)\[([\d,]+)\]\S* "
                         r"([\w\-]+)\(", entry):
        name, dtype, dims, op = m.groups()
        size = 1
        for n in dims.split(","):
            size *= int(n)
        if size >= heads * t * d and op not in ("parameter",
                                                "get-tuple-element"):
            big.append((op, dtype, dims, name))
    assert not big, big


def test_head_64_attention_compiles_as_a_kernel(one_chip):
    """The LFM2 cell's full layer (8192 tokens, 32 query heads over 8
    key/value heads of 64): the splash kernel over heads padded to the
    lanes, forward and backward."""
    from mxnet_tpu.ops.banded_attention import banded_attention

    def loss(q, k, v):
        return banded_attention(q, k, v, backend="splash") \
            .astype(jnp.float32).sum()

    q, k, v = _shapes(one_chip, ((1, 32, 8192, 64), jnp.bfloat16),
                      ((1, 8, 8192, 64), jnp.bfloat16),
                      ((1, 8, 8192, 64), jnp.bfloat16))
    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, v).compile() \
        .as_text()
    assert text.count("tpu_custom_call") >= 2


def test_latent_attention_compiles_as_a_kernel(one_chip):
    """The Ling cell's latent layer (4096 tokens, 32 heads whose scores
    are 192 wide and whose values are 128 wide): the splash kernel over
    q and k padded to 256 lanes, the values' own width, forward and
    backward."""
    from mxnet_tpu.ops.banded_attention import banded_attention

    def loss(q, k, v):
        return banded_attention(q, k, v, backend="splash") \
            .astype(jnp.float32).sum()

    q, k, v = _shapes(one_chip, ((1, 32, 4096, 192), jnp.bfloat16),
                      ((1, 32, 4096, 192), jnp.bfloat16),
                      ((1, 32, 4096, 128), jnp.bfloat16))
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, v).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_the_delta_rule_compiles_as_a_kernel_pair(one_chip):
    """The Ling cell's mixer (4096 tokens, 32 heads of 128, bfloat16 q,
    k, v, float32 decay and beta) under ``jax.checkpoint``, as the
    mixer that holds the call rematerialises it, forward and backward:
    the Pallas pair (the forward, which keeps the states entering the
    chunks, and the backward) and no token-by-token state: nothing of
    T x d x d a head (the recurrence's states, 8.6 GB), and less beside
    operands and results than a quarter of the chip."""
    from mxnet_tpu.ops.kda import kda

    shapes = _shapes(one_chip, *([((1, 4096, 32, 128), jnp.bfloat16)] * 3),
                     ((1, 4096, 32, 128), jnp.float32),
                     ((1, 4096, 32), jnp.float32),
                     ((1, 4096, 32, 128), jnp.bfloat16))
    compiled = jax.jit(lambda q, k, v, log_a, beta, do: jax.vjp(
        jax.checkpoint(functools.partial(kda, backend="kernel")), q, k, v,
        log_a, beta)[1](do)).lower(*shapes).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "4096,32,128,128" not in text and "4096,1,32,128,128" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9


def test_the_delta_rule_mixer_hands_its_operands_straight_on(one_chip):
    """The Ling cell's whole mixer (4096 tokens of 2560, 32 heads of
    128, bfloat16) under the scope ``kda``, result and gradients, both
    kernel pairs: the operands' pair ``kda_mix_fwd`` / ``kda_mix_bwd``
    under ``kda`` > ``mix`` in its metadata (what
    ``kda_mix_ms_per_step`` reads), and every ``kda_fwd`` reading q, k,
    v and the decay as ``kda_mix_fwd`` wrote them: no copy or transpose
    of a (4096, 4096) array between the two, and none under ``mix``."""
    from mxnet_tpu.models import ling
    t, heads, d, units = 4096, 32, 128, 2560
    width = heads * d
    bf16, f32 = jnp.bfloat16, jnp.float32
    mixer = functools.partial(ling._kda_mixer, heads=heads, lower_bound=-5.0,
                              eps=1e-6, backend="kernel", mix="kernel")

    def both(*args):
        with jax.named_scope("kda"):
            out, vjp = jax.vjp(mixer, *args[:-1])
            return (out,) + vjp(args[-1])

    args = _shapes(one_chip, ((1, t, units), bf16),
                   *[((width, units), bf16)] * 4,
                   *[((heads, units), bf16)] * 2, ((units, width), bf16),
                   *[((width, 4), bf16)] * 3, ((heads,), f32),
                   ((width,), f32), ((d,), f32), ((1, t, units), bf16))
    entry = jax.jit(both).lower(*args).compile().as_text()
    entry = entry[entry.index("ENTRY"):]
    lines = {m.group(1): m.group(0) for m in re.finditer(
        r"%([\w.\-]+) = [^\n]*", entry)}

    def op_name(line):
        return re.search(r'op_name="([^"]*)"', line).group(1).split("/")

    def under(path, *names):
        segments = iter(path)
        return all(any(s == name for s in segments) for name in names)

    calls = {kernel: [name for name in lines if name.startswith(kernel + ".")
                      and "custom-call(" in lines[name]]
             for kernel in ("kda_mix_fwd", "kda_mix_bwd", "kda_fwd")}
    # the primal forward and its rematerialisation; one backward
    assert len(calls["kda_mix_fwd"]) == 2 and len(calls["kda_mix_bwd"]) == 1
    for name in calls["kda_mix_fwd"] + calls["kda_mix_bwd"]:
        assert under(op_name(lines[name]), "kda", "mix"), lines[name][:300]
    for name in calls["kda_fwd"]:
        operands = re.search(r"custom-call\(([^)]*)\)", lines[name]).group(1)
        for operand in re.findall(r"%([\w.\-]+)", operands)[:4]:
            assert re.search(r"get-tuple-element\(%kda_mix_fwd\.",
                             lines[operand]), lines[operand][:300]
    for name, line in lines.items():
        m = re.match(r"%\S+ = \w+\[([\d,]+)\]\S* (copy|transpose)\(", line)
        if m and math.prod(int(n) for n in m.group(1).split(",")) \
                >= t * width and "op_name" in line:
            assert not under(op_name(line), "mix"), line[:300]


def _mamba_mixer_program(one_chip, backend):
    """The Nemotron cell's Mamba-2 mixer (8192 tokens of 2688, 64 heads
    of 64, B and C in 8 groups of a 128-wide state, bfloat16) under the
    scope ``mamba``, result and gradients, compiled with the SSD's
    ``backend``."""
    from mxnet_tpu.models import nemotron_h
    t, units, heads, groups, state = 8192, 2688, 64, 8, 128
    inner, width = heads * 64, heads * 64 + 2 * groups * state
    bf16, f32 = jnp.bfloat16, jnp.float32
    mixer = functools.partial(nemotron_h._mamba_mixer, heads=heads,
                              groups=groups, state=state, eps=1e-5,
                              backend=backend)

    def both(*args):
        with jax.named_scope("mamba"):
            out, vjp = jax.vjp(mixer, *args[:-1])
            return (out,) + vjp(args[-1])

    args = _shapes(one_chip, ((1, t, units), bf16),
                   ((inner + width + heads, units), bf16),
                   ((width, 4), bf16), ((width,), bf16),
                   *[((heads,), f32)] * 3, ((inner,), f32),
                   ((units, inner), bf16), ((1, t, units), bf16))
    return jax.jit(both).lower(*args).compile()


def test_the_mamba_mixer_compiles_at_the_cells_widths(one_chip):
    """The mixer on the XLA scan: the SSD's chunk products lie under
    ``mamba`` > ``scan`` in the metadata (what ``ssd_scan_ms_per_step``
    reads), and the mixer, one rematerialised unit, needs under 3 GB of
    temporaries."""
    compiled = _mamba_mixer_program(one_chip, "chunked")
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9
    paths = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    assert any("/mamba/" in p and "/scan/" in p and "jit(ssd_packed)" in p
               and p.endswith("dot_general") for p in paths)
    assert any("/mamba/" in p and "/conv/" in p for p in paths)


def test_the_mamba_mixer_takes_the_ssd_kernel_pair(one_chip):
    """The mixer on the kernel pair: the primal ``ssd_fwd``, the one that
    keeps the states under the backward pass's rematerialisation and
    ``ssd_bwd``, all three under ``mamba`` > ``scan`` in the metadata;
    each reads x, B and C as the filter wrote them, one array (no slice
    copied out of it); no array of a chunk's decays (L x L a head and
    chunk) anywhere; and the mixer needs under 1.6 GB of temporaries
    (2.07 GB on the XLA scan)."""
    compiled = _mamba_mixer_program(one_chip, "kernel")
    entry = compiled.as_text()
    entry = entry[entry.index("ENTRY"):]
    lines = {m.group(1): m.group(0) for m in re.finditer(
        r"%([\w.\-]+) = [^\n]*", entry)}
    calls = {kernel: [name for name in lines if name.startswith(kernel + ".")
                      and "custom-call(" in lines[name]]
             for kernel in ("ssd_fwd", "ssd_bwd")}
    assert len(calls["ssd_fwd"]) == 2 and len(calls["ssd_bwd"]) == 1
    for name in calls["ssd_fwd"] + calls["ssd_bwd"]:
        path = re.search(r'op_name="([^"]*)"', lines[name]).group(1)
        assert "/mamba/" in path and "/scan/" in path, path
        operands = re.findall(r"%([\w.\-]+)", re.search(
            r"custom-call\(([^)]*)\)", lines[name]).group(1))
        assert operands[0] == operands[1] == operands[2], lines[name][:300]
    assert not re.search(r"f32\[[\d,]*128,128\]", entry)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6e9


def test_short_conv_compiles_without_a_convolution(one_chip):
    """The LFM2 cell's gated short convolution (8192 tokens, 2048
    channels, bfloat16): fusions of shifted adds, no convolution
    instruction and no array of windows (a (T, 3, C) or (3, T, C) copy
    of the input). Forward is one fusion with no temporary at all; the
    backward pass keeps less beside its operands and results than the
    three thirds of ``du`` and one float32 array of (T, C)."""
    from mxnet_tpu.ops.short_conv import short_conv

    u, w, g = _shapes(one_chip, ((1, 8192, 6144), jnp.bfloat16),
                      ((2048, 3), jnp.bfloat16),
                      ((1, 8192, 2048), jnp.bfloat16))
    forward = jax.jit(short_conv).lower(u, w).compile()
    backward = jax.jit(lambda u, w, g: jax.vjp(short_conv, u, w)[1](g)) \
        .lower(u, w, g).compile()
    for compiled in (forward, backward):
        text = compiled.as_text()
        assert "convolution(" not in text
        assert "8192,3,2048" not in text and "3,8192,2048" not in text
    assert forward.memory_analysis().temp_size_in_bytes == 0
    assert backward.memory_analysis().temp_size_in_bytes \
        < 8192 * 2048 * (3 * 2 + 4)


def test_routed_experts_compile_without_a_dense_product(one_chip):
    """The Laguna cell's expert layer (8192 tokens, 8 of 256 experts a
    token, 32 held): the grouped products are kernels over the sorted
    rows, and no product of rows x experts x width is in the program."""
    from mxnet_tpu.parallel.moe import routed_experts

    def loss(x, r, g, u, d):
        return routed_experts(x, r, g, u, d, k=8, held_start=0,
                              num_held=32, scale=2.5) \
            .astype(jnp.float32).sum()

    args = _shapes(one_chip, ((8192, 2048), jnp.bfloat16),
                   ((256, 2048), jnp.float32),
                   ((32, 2048, 512), jnp.bfloat16),
                   ((32, 2048, 512), jnp.bfloat16),
                   ((32, 512, 2048), jnp.bfloat16))
    compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(*args) \
        .compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the whole worst-case buffer (65536 rows) through the three
    # products, forward and backward, is 9 x 2 x 65536 x 2048 x 512;
    # every row through every held expert would be 32 times that
    cost = compiled.cost_analysis()
    assert cost["flops"] < 2 * 9 * 2 * 65536 * 2048 * 512


@pytest.fixture(scope="module")
def expert_layer_text(one_chip):
    """The Laguna cell's expert layer, result and gradients, compiled:
    the optimized HLO."""
    from mxnet_tpu.parallel.moe import routed_experts

    def loss(x, r, g, u, d):
        out = routed_experts(x, r, g, u, d, k=8, held_start=0,
                             num_held=32, scale=2.5)
        return out.astype(jnp.float32).sum(), out

    args = _shapes(one_chip, ((8192, 2048), jnp.bfloat16),
                   ((256, 2048), jnp.float32),
                   ((32, 2048, 512), jnp.bfloat16),
                   ((32, 2048, 512), jnp.bfloat16),
                   ((32, 512, 2048), jnp.bfloat16))
    return jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3, 4), has_aux=True)) \
        .lower(*args).compile().as_text()


def test_routed_experts_hold_no_array_of_the_worst_case_rows(
        expert_layer_text):
    """The buffer of sorted rows has 16,384 rows in the cell (twice the
    8,192 that uniform routing sends to 32 of 256 experts): neither the
    first pass nor the further passes, forward or backward, make an
    array of 65,536 rows by the model's or the experts' width, and the
    (token, choice) index vectors are all that has 65,536 rows."""
    from mxnet_tpu.parallel.moe import buffer_rows
    assert buffer_rows(8192 * 8, 32, 256) == 16384
    text = expert_layer_text
    assert re.search(r"bf16\[16384,2048\]", text)
    assert re.search(r"bf16\[16384,512\]", text)
    worst = re.findall(r"\w+\[(?:65536,(?:8,)?(?:2048|512)"
                       r"|8192,8,(?:2048|512))\]", text)
    assert not worst, sorted(set(worst))


def test_routed_experts_take_further_passes_under_a_conditional(
        expert_layer_text):
    """One program whatever the routing: the further passes of the
    buffer are a loop inside a conditional on the rows routed here, one
    in the forward pass and one in the backward pass (which recomputes
    each further pass where it transposes it)."""
    text = expert_layer_text
    conditionals = re.findall(
        r" conditional\(.*?op_name=\"([^\"]*)\"", text)
    assert len(conditionals) == 2, conditionals
    assert sum("transpose(" in name for name in conditionals) == 1
    assert len(re.findall(r" while\(", text)) == 2
