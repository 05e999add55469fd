"""Compile the main paths' device programs with the TPU's own compiler, for
a v5e chip that is described and not attached (on-chip-measurement guide,
section 2). Nothing runs: these catch what the chip's compiler refuses —
a kernel's tiling, its fast-memory budget, a program that cannot be
partitioned — before a chip call is spent on it.

The topology is described inside a module-scoped fixture, never at import:
only one process may load libtpu, and every xdist worker imports this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

GPTJ = dict(
    vocab_size=50400, d_model=4096, n_heads=16, d_ff=16384, max_seq_len=2048,
    parallel_block=True, use_swiglu=False,
)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu from describing it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry written for a described chip cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernels(text):
    """The Pallas kernels of a compiled program, by the names the trace will
    show (an instruction's name less XLA's counter), sorted."""
    import re

    called = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    return sorted(re.sub(r"\.\d+$", "", name) for name in called)


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree
    )


@pytest.mark.parametrize(
    "head_dim,heads,seq", [(256, 16, 2048), (128, 32, 2048), (64, 16, 1024)]
)
def test_flash_attention_forward_and_backward(one_chip, head_dim, heads, seq):
    """The kernel ``ops.attention`` picks on a TPU, with its tuned blocks,
    at GPT-J's head_dim 256 (seq 2048), Llama's 128 and the small 64."""
    from ray_tpu.ops.attention import _flash

    q = jax.ShapeDtypeStruct((1, seq, heads, head_dim), jnp.bfloat16, sharding=one_chip)
    flash = functools.partial(_flash, causal=True)
    fwd = jax.jit(flash).lower(q, q, q).compile()
    assert "tpu_custom_call" in fwd.as_text()

    def loss(q, k, v):
        return flash(q, k, v).astype(jnp.float32).sum()

    bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).compile()
    assert _kernels(bwd.as_text()) == ["flash_attention_fwd", "flash_mha_bwd"]  # the one-pass backward


def test_flash_module_is_the_same_wherever_it_is_called_from(one_chip):
    """The kernel's module travels as an opaque string with its Python call
    stack inside, which the compile cache's key cannot strip: by default the
    lowered program differs with the caller's line. ``chip_smoke.py`` and
    ``bench.py`` export ``JAX_TRACEBACK_IN_LOCATIONS_LIMIT=0`` so that a moved
    checkout or an edited script still finds the step it compiled before."""
    import re

    from ray_tpu.ops.attention import _flash

    q = jax.ShapeDtypeStruct((1, 2048, 16, 256), jnp.bfloat16, sharding=one_chip)

    def lowered():
        jax.clear_caches()  # or the kernel's own jit answers from its first trace
        return jax.jit(functools.partial(_flash, causal=True)).lower(q, q, q).as_text()

    here = lowered()
    there = lowered()  # the same program, called from the next line
    assert here != there
    for launcher in ("chip_smoke.py", "bench.py"):
        with open(os.path.join(os.path.dirname(__file__), "..", launcher)) as f:
            (limit,) = re.findall(r'"JAX_TRACEBACK_IN_LOCATIONS_LIMIT", "(\d+)"', f.read())
        jax.config.update("jax_traceback_in_locations_limit", int(limit))
        try:
            here = lowered()
            there = lowered()
        finally:
            jax.config.update("jax_traceback_in_locations_limit", 10)  # JAX's default
        assert here == there, launcher


# serve.llm's two published geometries of the GPT-J/Llama kind
WIDTHS = {
    "gptj": GPTJ,  # 16 heads of 256
    "llama": dict(vocab_size=32000, d_model=4096, n_heads=32, d_ff=11008, max_seq_len=2048),  # Llama-2-7B: 32 of 128
}


def _computations(text):
    """({computation: [(name, dims, layout, op, operands and attributes)]} of a
    compiled program's instructions with an array result, the names of the
    fused computations)."""
    import re

    bodies, body = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            body = bodies.setdefault(head.group(1), [])
        made = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\](\S*) ([\w\-]+)\((.*)", line)
        if made and body is not None:
            body.append(made.groups())
    return bodies, set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", text))


def _alone(text):
    """(dims, layout, op) of every instruction that runs by itself. What stands
    inside a fused computation is the fusion's own arithmetic: a
    ``dynamic-slice`` there is the fusion reading its slice in place."""
    bodies, fused = _computations(text)
    return [(dims, layout, op) for comp, body in bodies.items() if comp not in fused for _, dims, layout, op, _ in body]


def _staged(text, params):
    """The instructions that make a copy of a layer's whole q, k or v
    projection ((D, heads, head_dim), or that with a leading 1), or that hold
    a layer of any stacked matrix in fast memory (``S(1)``)."""
    layer = {name: ",".join(map(str, x.shape[1:])) for name, x in params.items() if x.ndim > 2}
    whole = {lead + layer[name] for name in ("wq", "wk", "wv") for lead in ("", "1,")}
    weights = {lead + dims for dims in layer.values() for lead in ("", "1,")}
    return [
        (dims, layout, op) for dims, layout, op in _alone(text)
        if dims in whole or (dims in weights and "S(1)" in layout)
    ]


@pytest.mark.parametrize("widths", list(WIDTHS))
def test_paged_decode_and_prefill_at_published_widths(one_chip, monkeypatch, widths):
    """serve.llm's device programs (``make_paged_fns``) at GPT-J-6B's and
    Llama-2-7B's published widths; 4 of the layers (the scan body is the
    same; a stack of 2 fits fast memory whole and the compiler then keeps
    ``wo`` there, which no deployment's depth allows), the parameters lying as
    the engine places them (``paged_layouts``). The decode steps hold the paged-attention kernel and
    read the pool nowhere else: no gathered copy of a table's rows, no layer
    cut out of the pool. No program stages a layer's q, k or v projection in
    fast memory before its dot: each reads its slice of the stacked tensor in
    place. The prefill (S is the bucket) stays on the gather path."""
    import re

    from jax.experimental.layout import Format, Layout

    from ray_tpu.models import generation as G
    from ray_tpu.models.transformer import TransformerConfig, init_params

    _steered_to_tpu(monkeypatch)
    cfg = TransformerConfig(n_layers=4, **WIDTHS[widths])
    heads, head_dim = cfg.kv_heads, cfg.head_dim
    block, blocks, batch, per_seq = 16, 384, 8, 64
    prefill, decode, decode_greedy = G.make_paged_fns(cfg, block_size=block)
    plain = _on(one_chip, jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    layouts = G.paged_layouts(cfg)
    assert sorted(layouts) == ["wk", "wq", "wv"]
    params = {
        name: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=Format(Layout(major_to_minor=layouts[name]), one_chip))
        if name in layouts else x for name, x in plain.items()
    }
    pool = _on(one_chip, jax.eval_shape(lambda: G.init_paged_pool(cfg, blocks, block)))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step_text(step, params):
        return step.lower(
            params, arg((batch,), jnp.int32), arg((batch,), jnp.int32),
            arg((batch, per_seq), jnp.int32), pool, arg((batch,), jnp.bool_),
        ).compile().as_text()

    # results that are a table's rows (B, 1024, heads, head_dim) or one layer of the pool
    slots = blocks * block
    unwanted = {f"[{batch},{per_seq * block},{heads},{head_dim}]"} | {
        f"[{lead}{slots},{heads},{head_dim}]" for lead in ("", "1,", "2,", "1,2,")}
    for step in (decode_greedy, decode):
        text = step_text(step, params)
        assert "tpu_custom_call" in text and "paged_decode_attention" in text
        made = re.findall(r"= \w+(\[[\d,]*\])\S* (?:gather|dynamic-slice|copy)\(", text)
        assert made and not unwanted & set(made)
        assert not _staged(text, plain)
    # the check sees what the placing removes: in the default layout each of
    # the three projections is staged whole, a fusion a projection
    assert [op for _, _, op in _staged(step_text(decode_greedy, plain), plain)] == ["fusion"] * 3
    for bucket in (256, 512):
        text = prefill.lower(
            params, arg((1, bucket), jnp.int32), arg((1, per_seq), jnp.int32), pool,
            arg((), jnp.int32),
        ).compile().as_text()
        assert "tpu_custom_call" not in text
        assert not _staged(text, plain)


def _kernel_dmas(lowered_text):
    """(starts, waits) of the one Pallas kernel a lowered program holds: each a list of (source shape, destination
    shape), read from the kernel's Mosaic module as the custom call carries it (the ``body`` of its configuration)."""
    import base64
    import re

    from jax._src.lib.mlir import ir

    (body,) = re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', lowered_text)
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True  # the module is in Mosaic's serialized dialect, which nothing here registers
    with ctx:
        module = ir.Module.parse(base64.b64decode(body)).operation.get_asm(print_generic_op_form=True)
    shapes = lambda line: re.findall(r"memref<([\dx]+)x\w+, #tpu.memory_space<(?:any|vmem)>>", line.split(" : ")[-1])
    starts = [tuple(shapes(line)) for line in module.splitlines() if ".enqueue_dma\"" in line]
    waits = [tuple(shapes(line)) for line in module.splitlines() if ".wait_dma2\"" in line]
    return starts, waits


@pytest.mark.parametrize("shape", ["falcon_h1_with_the_steps_row", "lfm2_with_the_steps_row", "gptj"])
def test_the_paged_kernel_brings_a_block_in_under_one_copy(one_chip, shape):
    """The lowered kernel at Falcon-H1's, LFM2's and GPT-J's shapes: one DMA
    start at each of ``chunk_walk``'s three start sites and one wait, each of a
    block's keys and values together (two planes of ``block_size`` x heads rows,
    16 KB a plane in the first two), and, where the call writes the step's own
    row, one start and one wait of the tiles that go back (both planes), where
    there were two of each. The pool is the call's last operand and, with rows,
    its second result in place."""
    from ray_tpu.ops.paged_attention import paged_decode_attention

    heads, kv, hd, blocks, per_seq, batch, flat = {
        "falcon_h1_with_the_steps_row": (20, 4, 128, 6145, 128, 48, True),
        "lfm2_with_the_steps_row": (32, 4, 128, 7681, 128, 48, True),
        "gptj": (16, 16, 256, 384, 64, 8, False),
    }[shape]

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(q, pool, tables, lengths, new_k, new_v):
        rows = dict(kv_heads=kv, new_k=new_k, new_v=new_v) if flat else {}
        return paged_decode_attention(q, pool, 1, tables, lengths, block_size=16, **rows)

    pool = arg((2, 2, blocks * 16 * kv, hd) if flat else (2, 2, blocks * 16, kv, hd))
    lowered = jax.jit(call, donate_argnums=(1,) if flat else ()).lower(
        arg((batch, heads, hd)), pool, arg((batch, per_seq), jnp.int32), arg((batch,), jnp.int32), arg((batch, kv, hd)),
        arg((batch, kv, hd)))
    starts, waits = _kernel_dmas(lowered.as_text())
    block = f"2x{16 * kv}x{hd}" if flat else f"2x16x{kv}x{hd}"
    back = [(f"2x16x{hd}",) * 2] if flat else []  # four heads of bfloat16: one sublane tile covers the row
    assert sorted(starts) == sorted([(block, block)] * 3 + back) and sorted(waits) == sorted([(block, block)] + back)
    compiled = lowered.compile()
    assert _kernels(compiled.as_text()) == ["paged_decode_attention"]
    if flat:
        assert compiled.memory_analysis().alias_size_in_bytes >= pool.size * 2  # the pool comes back in place


def _latent_kernel_reads_the_pool_in_place(text, pool_dims, batch, per_seq, block):
    """The decode step holds the latent kernel (``paged_latent_attention``, a
    call a section's attention) and reads the pool nowhere else: no table's
    blocks gathered or copied (``[batch, per_seq, block, 640]``), and the pool
    itself scattered into in place, never copied or re-laid out."""
    import re

    assert "tpu_custom_call" in text and "paged_latent_attention" in text
    made = re.findall(r"= \w+(\[[\d,]*\])\S* (?:gather|dynamic-slice|copy|transpose)\(", text)
    assert made and f"[{batch},{per_seq},{block},640]" not in made and f"[{batch},{per_seq * block},640]" not in made
    assert pool_dims not in made


def _latent_kernel_waits_for_a_chunk_by_its_bytes(one_chip, attentions, blocks, block, batch, per_seq):
    """The latent kernel alone at a cell's shapes (PR 63): a copy a block at
    each place of a chunk at ``chunk_walk``'s three start sites, and at the one
    site that waits no wait a block (32 before, when a chunk was 32 blocks) but
    one a binary digit of a chunk's live count, each for that many blocks'
    bytes, the whole chunk's first: seven for a chunk of 64 blocks."""
    from ray_tpu.ops.paged_attention import _LATENT_CHUNK_BYTES, chunk_blocks_for, paged_latent_attention

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = jax.jit(lambda q_l, q_r, pool, tables, lengths: paged_latent_attention(
        q_l, q_r, pool, jnp.int32(1), tables, lengths, scale=192 ** -0.5)).lower(
        arg((batch, 64, 512)), arg((batch, 64, 64)), arg((attentions, blocks, block, 640)), arg((batch, per_seq), jnp.int32),
        arg((batch,), jnp.int32))
    starts, waits = _kernel_dmas(lowered.as_text())
    chunk = chunk_blocks_for(per_seq, block * 640 * 2, _LATENT_CHUNK_BYTES)
    assert chunk == 64 and starts == [(f"{block}x640",) * 2] * (3 * chunk)
    assert waits == [(f"{n * block}x640",) * 2 for n in (64, 32, 16, 8, 4, 2, 1)]
    assert _kernels(lowered.compile().as_text()) == ["paged_latent_attention"]


def _grouped_matmuls_take(text, rows, width, all_rows):
    """The program's three grouped matmuls (``moe.grouped_matmul``: on a TPU
    at these widths JAX's Pallas ``gmm``, a Mosaic call each) multiply ``rows``
    compacted rows, gate and up from ``[rows, width]`` operands and down back
    to that width in float32, reading the experts out of their stack
    (``[layers x held, ...]`` operands: no layer cut out); and no gather makes
    a row a (token, choice) pair of the batch (bfloat16 ``[all_rows,
    width]``): the window's rows alone are gathered for the matmuls (the
    float32 gather of that many rows in a decode step is the combine's, of the
    results: ``moe.combine``)."""
    import re

    calls = [line for line in text.splitlines() if re.search(r"%gmm[.\d]* = ", line)]
    made = [re.search(r"= (\w+)\[(\d+),(\d+)\]", line).groups() for line in calls]
    assert len(calls) == 3 and all('custom_call_target="tpu_custom_call"' in line for line in calls)
    _nothing_is_copied_for_the_grouped_matmuls(text)
    assert "ragged-dot" not in text
    assert sorted(int(n) for _, n, _ in made) == [rows] * 3 and ("f32", str(rows), str(width)) in made
    assert sum(f"bf16[{rows},{width}]" in line for line in calls) == 2
    assert str(all_rows) not in re.findall(rf"= bf16\[(\d+),{width}\]\S* gather\(", text)


def _nothing_is_copied_for_the_grouped_matmuls(text, but_the_metadata=False):
    """No operand of a ``gmm`` call is made by a copy (``copy``, ``copy-done``:
    an expert stack, a window's rows or the group metadata staged or laid out
    anew ahead of the kernel), and no instruction of its own makes an expert
    stack's dims: the kernel reads the stack where it lies, whatever its weight
    tile and the fast memory it states. ``but_the_metadata``: the call's first
    four operands (the group metadata: vectors of a kilobyte or two) may be
    staged, as the compiler does for Granite's prefill of 40 row tiles by 360
    groups."""
    import re

    made_by = {name: op for name, op in re.findall(r"%([\w.\-]+) = [^=\n]*? ([\w\-]+)\(", text)}
    calls = [line for line in text.splitlines() if re.search(r"%gmm[.\d]* = ", line)]
    assert calls
    stacks = set()
    for line in calls:
        operands = re.findall(r"%([\w.\-]+)", line.split("custom-call(", 1)[1].split(")", 1)[0])
        assert len(operands) == 6
        assert not [o for o in operands[4 * but_the_metadata:] if made_by.get(o, "").startswith("copy")], line[:300]
        stacks.add(re.search(r"bf16\[(\d+,\d+,\d+)\]", line.split("operand_layout_constraints", 1)[1]).group(1))
    assert not [(dims, op) for dims, _, op in _alone(text)
                if dims in stacks and op not in ("parameter", "bitcast", "get-tuple-element")]


def _stated_tilings(monkeypatch):
    """The tilings ``moe.grouped_matmul`` hands the grouped kernel while a
    program is traced, as a set that fills as the programs are lowered: a
    decode step's windows and every window of Kimi-K2 are one row tile of the
    operand's rows, a window of 512 rows goes under two tiles of
    ``moe.ROW_TILE`` (under four of half that in Granite's and Nemotron's
    decode steps, where an expert gets a few rows); under a row tile at the ridge, and where it fits one
    whole, an expert's matrix goes by in the fewest tiles of at most 8 MB
    (PR 51: in 2 MB tiles before, as under a smaller row tile still)."""
    from ray_tpu.models import moe

    stated, gmm = set(), moe._gmm

    def recorded(*args, tiling, **kwargs):
        stated.add(tiling)
        return gmm(*args, tiling=tiling, **kwargs)

    monkeypatch.setattr(moe, "_gmm", recorded)
    return stated


def test_latent_decode_and_prefill_at_longcat_widths(one_chip, monkeypatch):
    """serve.llm's programs for LongCat-Flash's language model at the published
    widths, one chip's share of the experts (16 of 512), 2 layers. The decode
    step holds the grouped matmuls over a window of 32 of its 384 (token,
    choice) rows (``moe.window_rows``; a 1,024 bucket's over 512 of 12,288,
    under row tiles of 256) and the latent kernel,
    which reads a table's live blocks where they lie (no gathered copy), and
    never re-lays the pool out: its rows are stored 640 wide (576
    values: the TPU gives such a pool another device layout than the one the
    program computes in, and copies it whole, in and out, every step). The
    prefill attends to its own rows and holds no kernel."""
    from ray_tpu.models import longcat as M, paged

    _steered_to_tpu(monkeypatch)
    stated = _stated_tilings(monkeypatch)
    cfg = M.LongcatConfig(vocab_size=16384, num_layers=2, experts_held=16)
    block, blocks, batch, per_seq = 16, 4097, 32, 128
    prefill, _, decode_greedy = paged.make_paged_fns(M.paged_layer, cfg, block_size=block)
    params = _on(one_chip, jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    pool = _on(one_chip, jax.eval_shape(lambda: M.init_paged_pool(cfg, blocks, block)))
    assert pool["latent"].shape == (4, blocks, block, 640) and M.paged_block_bytes(cfg, block) == 4 * 16 * 1280

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = decode_greedy.lower(
        params, arg((batch,), jnp.int32), arg((batch,), jnp.int32), arg((batch, per_seq), jnp.int32), pool,
        arg((batch,), jnp.bool_),
    ).compile().as_text()
    _grouped_matmuls_take(text, 32, 6144, batch * cfg.moe_topk)  # gate, up and down of the held experts
    assert stated == {(32, 512, 2048), (32, 2048, 512)}  # one row tile, the window's rows; 2 MB tiles: the parent's statement
    _latent_kernel_reads_the_pool_in_place(text, f"[4,{blocks},{block},640]", batch, per_seq, block)
    _latent_kernel_waits_for_a_chunk_by_its_bytes(one_chip, 4, blocks, block, batch, per_seq)
    stated.clear()
    text = prefill.lower(
        params, arg((1, 1024), jnp.int32), arg((1, per_seq), jnp.int32), pool, arg((), jnp.int32)).compile().as_text()
    _grouped_matmuls_take(text, 512, 6144, 1024 * cfg.moe_topk)
    assert stated == {(256, 2048, 2048)}  # the operand's 512 rows under two row tiles; 25 MB a matrix in three tiles
    assert "paged_latent_attention" not in text
    assert "gather(" not in "".join(line for line in text.splitlines() if f",{block},640]" in line)


def test_latent_decode_and_prefill_at_kimi_k2_widths(one_chip, monkeypatch):
    """serve.llm's programs for Kimi-K2's language model as the benchmark's
    configuration cuts it (``benchmarks/configs/kimi-k2-7l.json``): the
    published widths, the dense layer and six expert layers as two scans in one
    program, 12 of 384 experts, an eighth of the vocabulary, the engine's 48
    slots over 7,681 blocks. 9.70 GB of weights and a 1.10 GB pool are the
    program's arguments. The decode step holds the grouped matmuls over a
    window of 32 of its 384 (token, choice) rows, and of its 512 at 64 slots
    (where the row tile had followed the batch to 512 and the step cost 25.5 ms
    for 15.5: PERF.md, section 6, PR 34 and PR 38), a 512 bucket's over 256
    of 4,096, and the
    latent kernel (a table's live blocks read where they lie: no gathered
    copy, and 5 MB of the program's own where the gathers had 40) and never
    copies the pool. **No weight is copied out of
    its stack before its matmul but the two that longcat's programs copy
    too**, in the one ``mla`` both kinds run (PERF.md, section 7): ``wqb``'s
    layer (a ``constant_dynamic-slice_fusion`` of 37.7 MB a layer, 0.69 ms a
    step on the chip; at 64 slots and in the prefill into fast memory,
    ``S(1)``; whichever way the matrix lies: a ``major_to_minor`` of (0, 2, 1)
    adds a copy to it, so the kind names no ``paged_layouts``) and ``wkvb``'s
    two halves (a head's key and value parts, cut along the minor axis, held
    in ``S(1)``). The dense layer's 18432-wide tensors, the shared expert's,
    ``wqa``, ``wkva``, ``wo``, the router and the experts are read where they
    lie."""
    from ray_tpu.models import kimi as M, paged

    _steered_to_tpu(monkeypatch)
    stated = _stated_tilings(monkeypatch)
    cfg = M.KimiConfig(vocab_size=20480, num_hidden_layers=7, experts_held=12)
    assert (cfg.first_k_dense_replace, cfg.n_expert_layers) == (1, 6) and not hasattr(M, "paged_layouts")
    block, blocks, batch, per_seq = 16, 7681, 48, 96
    prefill, _, decode_greedy = paged.make_paged_fns(M.paged_layer, cfg, block_size=block)
    params = _on(one_chip, jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    pool = _on(one_chip, jax.eval_shape(lambda: M.init_paged_pool(cfg, blocks, block)))
    assert pool["latent"].shape == (7, blocks, block, 640) and M.paged_block_bytes(cfg, block) == 7 * 16 * 1280
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert 9.69e9 < nbytes < 9.71e9 and 1.10e9 < blocks * M.paged_block_bytes(cfg, block) < 1.11e9

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stacked = {lead + ",".join(map(str, x.shape[1:])): name for name, x in params.items()
               if x.ndim > 2 for lead in ("", "1,")}

    def staged(text):
        """Names of the stacked tensors a layer of which some instruction of its
        own makes: a copy out of the stack, into fast memory or not."""
        return {stacked[dims] for dims, layout, op in _alone(text)
                if dims in stacked and op not in ("parameter", "bitcast", "get-tuple-element")}

    compiled = decode_greedy.lower(
        params, arg((batch,), jnp.int32), arg((batch,), jnp.int32), arg((batch, per_seq), jnp.int32), pool,
        arg((batch,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    _grouped_matmuls_take(text, 32, 7168, batch * cfg.num_experts_per_tok)  # gate, up and down of the held experts
    _latent_kernel_reads_the_pool_in_place(text, f"[7,{blocks},{block},640]", batch, per_seq, block)
    _latent_kernel_waits_for_a_chunk_by_its_bytes(one_chip, 7, blocks, block, batch, per_seq)
    assert staged(text) == {"wqb", "wkvb"}
    assert compiled.memory_analysis().temp_size_in_bytes < 0.02e9  # beside 10.8 GB of arguments
    text = decode_greedy.lower(
        params, arg((64,), jnp.int32), arg((64,), jnp.int32), arg((64, per_seq), jnp.int32), pool,
        arg((64,), jnp.bool_),
    ).compile().as_text()
    _grouped_matmuls_take(text, 32, 7168, 64 * cfg.num_experts_per_tok)
    assert staged(text) == {"wqb", "wkvb"}
    compiled = prefill.lower(
        params, arg((1, 512), jnp.int32), arg((1, per_seq), jnp.int32), pool, arg((), jnp.int32)).compile()
    text = compiled.as_text()
    _grouped_matmuls_take(text, 256, 7168, 512 * cfg.num_experts_per_tok)
    # every window of the kind is one row tile of its own rows: the decode programs state what the parent's did; at
    # the ridge 29 MB a matrix goes by in four tiles of 7.3 MB
    assert stated == {(32, 512, 2048), (32, 2048, 512), (256, 1792, 2048), (256, 2048, 1792)}
    assert "paged_latent_attention" not in text
    assert "gather(" not in "".join(line for line in text.splitlines() if f",{block},640]" in line)
    assert staged(text) <= {"wqb", "wkvb"}


def test_hybrid_decode_and_prefill_at_olmo_hybrid_widths(one_chip, monkeypatch):
    """serve.llm's programs for Olmo-Hybrid-7B as the benchmark's configuration
    cuts it (``benchmarks/configs/olmo-hybrid-7b-16l.json``): the published
    widths, 16 of 32 layers as four periods in one scan, the whole vocabulary,
    the engine's 48 slots over 4,609 blocks and 49 state rows. The file's
    arithmetic against the compiler: 8.20 GB of weights, a 4.83 GB K/V pool of
    32 stored heads and 1.35 GB of state rows are the programs' arguments, and
    each program's own memory is megabytes (the decode step 34 MB, the prefill
    of 512 44 MB: with a layer of the K/V pool gathered for the prefill's
    attention it was 1.2 GB, and with the output gate cut into heads of 192
    lanes the whole ``gdn_gate`` stack was re-laid, 0.5 GB). The decode step
    holds both kernels, ``gated_delta_update`` a linear layer and
    ``paged_decode_attention`` a full one, and makes no copy of the state
    pool, of the window pool or of a K/V layer. The prefill holds neither
    kernel, gathers nothing out of the K/V pool and solves its chunks without a
    triangular-solve custom-call."""
    import json
    import re

    from benchmarks.families import olmo_hybrid as family
    from ray_tpu.models import olmo_hybrid as M, paged
    from ray_tpu.serve.llm.deployment import _resolve_model_cfg

    _steered_to_tpu(monkeypatch)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs", "olmo-hybrid-7b-16l.json")) as f:
        config = json.load(f)
    cfg = _resolve_model_cfg(family.model_kwargs(config))
    e = config["engine"]
    block, blocks, batch, per_seq = e["block_size"], e["num_blocks"], e["max_batch"], e["max_blocks_per_seq"]
    assert (cfg.n_linear, cfg.n_full, cfg.period, cfg.kv_heads_stored) == (12, 4, M.PERIOD, 32)
    prefill, _, decode_greedy = paged.make_paged_fns(M.paged_layer, cfg, block_size=block, state_rows=True)
    params = _on(one_chip, jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    pool = _on(one_chip, jax.eval_shape(lambda: M.init_paged_pool(cfg, blocks, block, batch + 1)))

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    assert 8.19e9 < nbytes(params) < 8.21e9
    assert 4.83e9 < nbytes(pool["kv"]) == blocks * M.paged_block_bytes(cfg, block) < 4.84e9
    rows = {name: nbytes(pool[name]) for name in ("state", "conv", "state_pos")}
    assert 1.35e9 < sum(rows.values()) == (batch + 1) * M.paged_state_bytes(cfg) < 1.36e9
    assert pool["state"].shape == (12, 49, 96, 5760) and pool["conv"].shape == (12, 49, 4 * 11520)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def pools_copied(text):
        """Instructions of their own that make a pool, or a layer of one, anew."""
        pools = {f"{lead}{dims}" for dims in ("49,96,5760", "49,46080", f"{blocks * block},32,128")
                 for lead in ("", "1,", "12,", "4,", "2,", "1,2,", "4,2,")}
        return [(dims, op) for dims, _, op in _alone(text)
                if dims in pools and op in ("copy", "transpose", "gather", "dynamic-slice")]

    compiled = decode_greedy.lower(
        params, arg((batch,), jnp.int32), arg((batch,), jnp.int32), arg((batch, per_seq), jnp.int32), pool,
        arg((batch,), jnp.bool_),
    ).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "gated_delta_update" in text and "paged_decode_attention" in text
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text)) == 4  # three linear layers and a full one, in the scan's body
    assert not pools_copied(text)
    assert 14.39e9 < mem.argument_size_in_bytes < 14.41e9 and mem.temp_size_in_bytes < 0.05e9
    assert mem.alias_size_in_bytes > 0.999 * nbytes(pool)  # the pool comes back in place
    compiled = prefill.lower(
        params, arg((1, 512), jnp.int32), arg((1, per_seq), jnp.int32), pool, arg((), jnp.int32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "gated_delta_update" not in text and "paged_decode_attention" not in text
    # the chunks' unit-triangular systems are solved by blocks, as products: until PR 48 each linear layer of the
    # period held a custom-call to "InvertDiagBlocksLowerTriangular" (what the TPU's compiler makes of
    # ``triangular_solve``: a walk a row at a time over every 64 x 64 block, a third of the prefill's device time)
    assert "InvertDiagBlocksLowerTriangular" not in text and "triangular" not in text.lower()
    assert not pools_copied(text)
    assert mem.temp_size_in_bytes < 0.08e9  # beside 14.4 GB of arguments in a chip of 15.75 usable


def test_phi4flash_decode_and_prefill_at_published_widths(one_chip, monkeypatch):
    """serve.llm's programs for Phi-4-mini-flash-reasoning whole, as the
    benchmark's configuration has it (``benchmarks/configs/phi4-mini-flash.json``):
    the published widths, all 32 layers as three sections, the whole vocabulary,
    the engine's 48 slots over 11,521 blocks of one stored layer and 49 state
    rows. The file's arithmetic against the compiler: 7.70 GB of weights, a 0.94
    GB shared K/V pool of ten flat pairs, 1.03 GB of rings and 0.16 GB of states
    are the programs' arguments. The decode step holds the three kernels
    (``selective_scan_update`` a state-space layer, ``ring_window_attention`` a
    window layer, ``paged_decode_attention`` the full and every cross layer),
    copies no pool, ring or state and scatters into no ring: the ring's kernel
    writes a step's row. A prefill of 2,048 holds the prompt's scan
    (``selective_scan_prefill``) and, for its last section on one position, the
    paged kernel; its own memory stays under 1 GB beside 9.9 GB of arguments."""
    import json
    import re

    from benchmarks.families import phi4flash as family
    from ray_tpu.models import paged, phi4flash as M
    from ray_tpu.serve.llm.deployment import _resolve_model_cfg

    _steered_to_tpu(monkeypatch)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs", "phi4-mini-flash.json")) as f:
        config = json.load(f)
    cfg = _resolve_model_cfg(family.model_kwargs(config))
    e = config["engine"]
    block, blocks, batch, per_seq = e["block_size"], e["num_blocks"], e["max_batch"], e["max_blocks_per_seq"]
    prefill, _, decode_greedy = paged.make_paged_fns(M.paged_layer, cfg, block_size=block, state_rows=True)
    params = _on(one_chip, jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    pool = _on(one_chip, jax.eval_shape(lambda: M.init_paged_pool(cfg, blocks, block, batch + 1)))

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    assert 7.69e9 < nbytes(params) < 7.72e9
    assert 0.94e9 < nbytes(pool["kv"]) == blocks * M.paged_block_bytes(cfg, block) < 0.95e9
    assert 1.02e9 < nbytes(pool["ring_k"]) + nbytes(pool["ring_v"]) == (batch + 1) * M.paged_ring(cfg)["bytes"] < 1.03e9
    rows = sum(nbytes(pool[name]) for name in ("state", "conv", "state_pos", "ring_k", "ring_v"))
    assert rows == (batch + 1) * M.paged_state_bytes(cfg) and 1.18e9 < rows < 1.21e9
    assert pool["state"].shape == (9, 49, 16, 5120) and pool["ring_k"].shape == (8, 49, 5120, 128)
    assert pool["kv"].shape == (1, 2, blocks * block * 10, 128)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def pools_copied(text):
        """Instructions of their own that make a pool, or a layer of one, anew."""
        pools = {f"{lead}{dims}" for dims in ("49,16,5120", "49,20480", "49,5120,128", f"{blocks * block * 10},128")
                 for lead in ("", "1,", "9,", "8,", "2,", "1,2,")}
        return [(dims, op) for dims, _, op in _alone(text)
                if dims in pools and op in ("copy", "transpose", "gather", "dynamic-slice")]

    def ring_writes(text):
        """The instructions, fused or alone, that write into a ring pool."""
        return re.findall(r"= bf16\[8,49,5120,128\]\S* (?:dynamic-update-slice|scatter)\(", text)

    def cache_writes(text):
        """The same for the shared cache's K and V."""
        return re.findall(rf"= bf16\[1,2,{blocks * block * 10},128\]\S* (?:dynamic-update-slice|scatter)\(", text)

    compiled = decode_greedy.lower(
        params, arg((batch,), jnp.int32), arg((batch,), jnp.int32), arg((batch, per_seq), jnp.int32), pool,
        arg((batch,), jnp.bool_),
    ).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert {"selective_scan_update", "ring_window_attention", "paged_decode_attention"} <= set(_kernels(text))
    # a scan's body holds its layers' kernels once: (ssm, ring), (ssm, paged), (paged)
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text)) == 5
    assert not pools_copied(text)
    assert 9.8e9 < mem.argument_size_in_bytes < 9.95e9 and mem.temp_size_in_bytes < 0.1e9
    assert mem.alias_size_in_bytes > 0.999 * nbytes(pool)  # the pool comes back in place
    # the ring's kernel writes a step's row itself: no scatter's loop over a ring is left in the step
    assert not ring_writes(text) and "ring_scatter" not in text  # in no instruction's ``op_name``
    # and the paged kernel the full layer's, into the cache it scores (the pools its outputs in place): nor over the cache
    assert not cache_writes(text) and "paged_scatter" not in text and " scatter(" not in text
    compiled = prefill.lower(
        params, arg((1, 2048), jnp.int32), arg((1, per_seq), jnp.int32), pool, arg((), jnp.int32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert len(ring_writes(text)) == 2 and "ring_scatter" in text  # a prompt's whole ring, K and V: one window a ring
    assert len(cache_writes(text)) == 2 and "paged_scatter" in text  # a prompt's blocks, K and V: ``write_spans`` stays
    assert {"selective_scan_prefill", "paged_decode_attention"} <= set(_kernels(text))
    # (by kernel, not by text: a helper first traced inside ``ring_window_attention`` by another test of this process
    # keeps that frame's name in the module's table of stack frames)
    assert not {"ring_window_attention", "selective_scan_update"} & set(_kernels(text))
    assert not pools_copied(text)
    assert mem.temp_size_in_bytes < 1.0e9


def test_exaone_moe_decode_and_prefill_at_published_widths(one_chip, monkeypatch):
    """serve.llm's programs for K-EXAONE-236B-A23B as the benchmark's
    configuration cuts it (``benchmarks/configs/k-exaone-236b-8l.json``): the
    published widths, layers 0-7 as three sections of whole periods (dense W;
    expert W W F; expert W W W F), 16 of 128 experts, an eighth of the
    vocabulary, the engine's 48 slots over 7,681 blocks of the two full layers
    and 49 state rows of six rings. The file's arithmetic against the compiler:
    11.96 GB of weights, a 1.01 GB K/V pool and 0.15 GB of rings are the
    programs' arguments, 13.1 GB, and the pool comes back in place. The decode
    step holds the ring's kernel a window layer (its plain form: it writes the
    step's row, so no scatter into a ring is left), the paged kernel a full
    layer over the flat pool of eight heads, and three grouped matmuls an expert
    layer over windows of 128 rows; no conditional (a ``lax.cond`` on the layer's
    kind copied both rings whole in its full branch), no pool or ring copied. A
    prefill of 1,024 walks windows of 512 rows through the grouped kernel, each
    under two row tiles of 256 (``moe.ROW_TILE``; no ``ragged-dot``), holds the
    flash kernel a full layer and writes each ring once; its own memory stays
    under 0.5 GB."""
    import json
    import re

    from benchmarks.families import exaone_moe as family
    from ray_tpu.models import exaone_moe as M, paged
    from ray_tpu.serve.llm.deployment import _resolve_model_cfg

    _steered_to_tpu(monkeypatch)
    stated = _stated_tilings(monkeypatch)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs", "k-exaone-236b-8l.json")) as f:
        config = json.load(f)
    cfg = _resolve_model_cfg(family.model_kwargs(config))
    e = config["engine"]
    block, blocks, batch, per_seq = e["block_size"], e["num_blocks"], e["max_batch"], e["max_blocks_per_seq"]
    prefill, _, decode_greedy = paged.make_paged_fns(M.paged_layer, cfg, block_size=block, state_rows=True)
    params = _on(one_chip, jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    pool = _on(one_chip, jax.eval_shape(lambda: M.init_paged_pool(cfg, blocks, block, batch + 1)))

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    assert 11.95e9 < nbytes(params) < 11.97e9
    assert 1.00e9 < nbytes(pool["kv"]) == blocks * M.paged_block_bytes(cfg, block) < 1.01e9
    rings = nbytes(pool["ring_k"]) + nbytes(pool["ring_v"])
    assert 0.15e9 < rings == (batch + 1) * M.paged_state_bytes(cfg) == (batch + 1) * M.paged_ring(cfg)["bytes"] < 0.16e9
    assert pool["kv"].shape == (2, 2, blocks * block * 8, 128) and pool["ring_k"].shape == (6, 49, 1024, 128)
    assert params["e_gate"].shape == (7, 16, 6144, 2048) and params["e_down"].shape == (7, 16, 2048, 6144)
    assert not hasattr(M, "paged_layouts")  # every projection's contraction lies in the tiles as it is stacked

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    flat, ring = f"{blocks * block * 8},128", "49,1024,128"

    def pools_copied(text):
        """Instructions of their own that make a pool, or a layer of one, anew."""
        pools = {f"{lead}{dims}" for dims in (flat, ring) for lead in ("", "1,", "2,", "6,", "1,2,", "2,2,")}
        return [(dims, op) for dims, _, op in _alone(text)
                if dims in pools and op in ("copy", "transpose", "gather", "dynamic-slice")]

    def ring_writes(text):
        return re.findall(rf"= bf16\[6,{ring}\]\S* (?:dynamic-update-slice|scatter)\(", text)

    def pool_writes(text):
        return re.findall(rf"= bf16\[2,2,{flat}\]\S* (?:dynamic-update-slice|scatter)\(", text)

    def gmm_rows(text):
        calls = [line for line in text.splitlines() if re.search(r"%gmm[.\d]* = ", line)]
        return sorted(int(re.search(r"= \w+\[(\d+),\d+\]", line).group(1)) for line in calls)

    compiled = decode_greedy.lower(
        params, arg((batch,), jnp.int32), arg((batch,), jnp.int32), arg((batch, per_seq), jnp.int32), pool,
        arg((batch,), jnp.bool_),
    ).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    kernels = _kernels(text)
    # a section's body holds its layers' kernels once: (W), (W W F), (W W W F); three grouped matmuls an expert layer
    assert (kernels.count("ring_window_attention"), kernels.count("paged_decode_attention"), kernels.count("gmm")) == (6, 2, 21)
    assert gmm_rows(text) == [128] * 21 and "ragged-dot" not in text and " conditional(" not in text
    assert stated == {(128, 512, 2048), (128, 2048, 512)}  # one row tile, the window's rows; 2 MB tiles: the parent's statement
    stated.clear()
    _nothing_is_copied_for_the_grouped_matmuls(text)
    assert not pools_copied(text)
    assert not ring_writes(text) and "ring_scatter" not in text  # the ring's kernel writes a step's row itself
    # and the paged kernel a full layer's, into the pools it scores (its outputs in place): no scatter over a pool is
    # left in the step (the expert layers' count of their groups' rows is the one scatter it has)
    assert not pool_writes(text) and "paged_scatter" not in text
    assert 13.0e9 < mem.argument_size_in_bytes < 13.2e9 and mem.temp_size_in_bytes < 0.1e9
    assert mem.alias_size_in_bytes > 0.999 * nbytes(pool)  # the pool comes back in place
    compiled = prefill.lower(
        params, arg((1, 1024), jnp.int32), arg((1, per_seq), jnp.int32), pool, arg((), jnp.int32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    kernels = _kernels(text)
    assert (kernels.count("flash_attention"), kernels.count("gmm")) == (2, 21) and gmm_rows(text) == [512] * 21
    assert stated == {(256, 2048, 2048)}  # the operand's 512 rows under two row tiles; three tiles of 8 MB a matrix
    _nothing_is_copied_for_the_grouped_matmuls(text)
    assert "ragged-dot" not in text and not {"ring_window_attention", "paged_decode_attention"} & set(kernels)
    assert len(ring_writes(text)) == 12 and "ring_scatter" in text  # a prompt's whole ring, K and V, a window layer
    assert len(pool_writes(text)) == 4 and "paged_scatter" in text  # a prompt's blocks, K and V, a full layer: ``write_spans`` stays
    assert not pools_copied(text)
    assert mem.temp_size_in_bytes < 0.5e9


def test_falcon_h1_decode_and_prefill_at_published_widths(one_chip, monkeypatch):
    """serve.llm's programs for Falcon-H1-34B-Instruct as the benchmark's
    configuration cuts it (``benchmarks/configs/falcon-h1-34b-6l.json``): the
    published widths, layers 0-5 of 72 as one section of a layer a call, the
    whole vocabulary untied, the engine's 48 slots over 6,145 blocks of every
    layer's four K/V heads and 49 state rows of six (256, 4096) float32 states.
    The file's arithmetic against the compiler: 10.51 GB of weights, a 1.21 GB
    K/V pool and 1.25 GB of state rows are the programs' arguments, 12.96 GB,
    and the pool comes back in place. The decode step's one layer body holds the
    paged kernel over the flat pool (it writes the step's row: no scatter over
    the pool is left) and the state's update (``selective_scan_update`` within
    its fast-memory budget: a 4 MB row in and out, twice buffered), and copies
    no pool, state or layer's matrix. A prefill of 1,024 holds the flash kernel,
    runs the recurrence as matrix products in ``ssd_prefill`` (eight chunks of
    128, a head's decays made in fast memory) and scatters the prompt's blocks;
    its own memory stays under 0.2 GB."""
    import json
    import re

    from benchmarks.families import falcon_h1 as family
    from ray_tpu.models import falcon_h1 as M, paged
    from ray_tpu.serve.llm.deployment import _resolve_model_cfg

    _steered_to_tpu(monkeypatch)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs", "falcon-h1-34b-6l.json")) as f:
        config = json.load(f)
    cfg = _resolve_model_cfg(family.model_kwargs(config))
    e = config["engine"]
    block, blocks, batch, per_seq = e["block_size"], e["num_blocks"], e["max_batch"], e["max_blocks_per_seq"]
    prefill, _, decode_greedy = paged.make_paged_fns(M.paged_layer, cfg, block_size=block, state_rows=True)
    params = _on(one_chip, jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    pool = _on(one_chip, jax.eval_shape(lambda: M.init_paged_pool(cfg, blocks, block, batch + 1)))

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    assert 10.50e9 < nbytes(params) < 10.52e9
    assert 1.20e9 < nbytes(pool["kv"]) == blocks * M.paged_block_bytes(cfg, block) < 1.21e9
    rows = sum(nbytes(pool[name]) for name in ("state", "conv", "state_pos"))
    assert 1.24e9 < rows == (batch + 1) * M.paged_state_bytes(cfg) < 1.25e9
    flat, state = f"{blocks * block * 4},128", "49,256,4096"
    assert pool["kv"].shape == (6, 2, blocks * block * 4, 128) and pool["state"].shape == (6, 49, 256, 4096)
    assert not hasattr(M, "paged_layouts")  # every projection's contraction lies in the tiles as it is stacked

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def pools_copied(text):
        """Instructions of their own that make a pool, or a layer of one, anew."""
        pools = {f"{lead}{dims}" for dims in (flat, state, "49,20480") for lead in ("", "1,", "6,", "2,", "1,2,", "6,2,")}
        return [(dims, op) for dims, _, op in _alone(text)
                if dims in pools and op in ("copy", "transpose", "gather", "dynamic-slice")]

    def pool_writes(text):
        return re.findall(rf"= bf16\[6,2,{flat}\]\S* (?:dynamic-update-slice|scatter)\(", text)

    def state_writes(text):
        return re.findall(rf"= f32\[6,{state}\]\S* (?:dynamic-update-slice|scatter)\(", text)

    compiled = decode_greedy.lower(
        params, arg((batch,), jnp.int32), arg((batch,), jnp.int32), arg((batch, per_seq), jnp.int32), pool,
        arg((batch,), jnp.bool_),
    ).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(text) == ["paged_decode_attention", "selective_scan_update"]  # one layer body: each once
    layers = {lead + ",".join(map(str, x.shape[1:])) for x in params.values() if x.ndim > 2 and x.shape[1] >= 4096
              for lead in ("", "1,")}  # the matrices
    assert not pools_copied(text)
    assert not [(dims, op) for dims, layout, op in _alone(text) if dims in layers and (op == "copy" or "S(1)" in layout)]
    # both kernels write where they read: no scatter over the K/V pool and no update-slice of a state is left
    assert not pool_writes(text) and "paged_scatter" not in text and not state_writes(text)
    assert 12.9e9 < mem.argument_size_in_bytes < 13.0e9 and mem.temp_size_in_bytes < 0.05e9
    assert mem.alias_size_in_bytes > 0.999 * nbytes(pool)  # the pool comes back in place
    compiled = prefill.lower(
        params, arg((1, 1024), jnp.int32), arg((1, per_seq), jnp.int32), pool, arg((), jnp.int32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(text) == ["flash_attention", "ssd_prefill"]
    assert len(pool_writes(text)) == 2 and "paged_scatter" in text  # a prompt's blocks, K and V: ``write_spans`` stays
    assert len(state_writes(text)) == 1  # the prompt's last state into its row
    assert not pools_copied(text)
    assert mem.temp_size_in_bytes < 0.2e9


def test_lfm2_moe_decode_and_prefill_at_published_widths(one_chip, monkeypatch):
    """serve.llm's programs for LFM2-24B-A2B as the benchmark's configuration
    cuts it (``benchmarks/configs/lfm2-24b-a2b-8l.json``): the published widths,
    layers 0-7 as three sections (dense C C; expert F C; expert C C F C), every
    one of a layer's 64 experts, the whole vocabulary, the engine's 48 slots
    over 7,681 blocks of the two full layers and 49 state rows of six conv
    windows. The file's arithmetic against the compiler: 8.05 GB of weights and
    a 0.50 GB K/V pool are the programs' arguments, and the pool comes back in
    place. The decode step holds the paged kernel a full layer over the flat
    pool of **two K/V heads of 64 to a row** (it writes the step's packed row,
    so no scatter over a pool is left) and **all three** grouped matmuls of an
    expert of 2048 x 1536 in the grouped kernel (each matrix one weight tile,
    6.3 MB), one window of the step's 192 rows, one row tile; no
    ``ragged-dot``. A prefill of 1,024 holds every
    expert's rows, 4,096, and hands them to the grouped kernel in one call
    under sixteen row tiles; it holds the flash kernel a full layer at a head
    of 64."""
    import json
    import re

    from benchmarks.families import lfm2_moe as family
    from ray_tpu.models import lfm2_moe as M, paged
    from ray_tpu.serve.llm.deployment import _resolve_model_cfg

    _steered_to_tpu(monkeypatch)
    stated = _stated_tilings(monkeypatch)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs", "lfm2-24b-a2b-8l.json")) as f:
        config = json.load(f)
    cfg = _resolve_model_cfg(family.model_kwargs(config))
    e = config["engine"]
    block, blocks, batch, per_seq = e["block_size"], e["num_blocks"], e["max_batch"], e["max_blocks_per_seq"]
    prefill, _, decode_greedy = paged.make_paged_fns(M.paged_layer, cfg, block_size=block, state_rows=True)
    params = _on(one_chip, jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    pool = _on(one_chip, jax.eval_shape(lambda: M.init_paged_pool(cfg, blocks, block, batch + 1)))

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    assert 8.04e9 < nbytes(params) < 8.06e9 and "unembed" not in params
    assert 0.50e9 < nbytes(pool["kv"]) == blocks * M.paged_block_bytes(cfg, block) < 0.51e9
    assert nbytes(pool["conv"]) + nbytes(pool["state_pos"]) == (batch + 1) * M.paged_state_bytes(cfg) == 49 * 6 * (12288 + 4)
    assert (cfg.head_dim, cfg.kv_pack) == (64, 2) and pool["kv"].shape == (2, 2, blocks * block * 4, 128)
    assert pool["conv"].shape == (6, 49, 6144)
    assert params["e_gate"].shape == (6, 64, 2048, 1536) and params["e_down"].shape == (6, 64, 1536, 2048)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    flat = f"{blocks * block * 4},128"

    def pools_copied(text):
        pools = {f"{lead}{flat}" for lead in ("", "1,", "2,", "1,2,", "2,2,")}
        return [(dims, op) for dims, _, op in _alone(text)
                if dims in pools and op in ("copy", "transpose", "gather", "dynamic-slice")]

    def pool_writes(text):
        return re.findall(rf"= bf16\[2,2,{flat}\]\S* (?:dynamic-update-slice|scatter)\(", text)

    def gmm_rows(text):
        calls = [line for line in text.splitlines() if re.search(r"%gmm[.\d]* = ", line)]
        return sorted(int(re.search(r"= \w+\[(\d+),\d+\]", line).group(1)) for line in calls)

    compiled = decode_greedy.lower(
        params, arg((batch,), jnp.int32), arg((batch,), jnp.int32), arg((batch, per_seq), jnp.int32), pool,
        arg((batch,), jnp.bool_),
    ).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    kernels = _kernels(text)
    # a section's body holds its layers' kernels once: (F C), (C C F C); three grouped matmuls an expert layer
    assert (kernels.count("paged_decode_attention"), kernels.count("gmm")) == (2, 18)
    assert gmm_rows(text) == [192] * 18 and "ragged-dot" not in text and " conditional(" not in text
    assert stated == {(192, 2048, 1536), (192, 1536, 2048)}  # gate and up; down: all three in the kernel, a matrix a tile
    stated.clear()
    _nothing_is_copied_for_the_grouped_matmuls(text)
    assert not pools_copied(text) and not pool_writes(text) and "paged_scatter" not in text and "paged_gather" not in text
    assert 8.5e9 < mem.argument_size_in_bytes < 8.7e9 and mem.temp_size_in_bytes < 0.2e9
    assert mem.alias_size_in_bytes > 0.999 * nbytes(pool)  # the pool comes back in place
    compiled = prefill.lower(
        params, arg((1, 1024), jnp.int32), arg((1, per_seq), jnp.int32), pool, arg((), jnp.int32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    kernels = _kernels(text)
    assert (kernels.count("flash_attention"), kernels.count("gmm")) == (2, 18) and gmm_rows(text) == [4096] * 18
    assert stated == {(256, 2048, 1536), (256, 1536, 2048)}  # one call of every row: no window is walked
    _nothing_is_copied_for_the_grouped_matmuls(text)
    assert "ragged-dot" not in text and "paged_decode_attention" not in kernels
    assert len(pool_writes(text)) == 4 and "paged_scatter" in text  # a prompt's blocks, K and V, a full layer
    assert not pools_copied(text)
    assert mem.temp_size_in_bytes < 0.5e9


def test_granite_hybrid_decode_and_prefill_at_published_widths(one_chip, monkeypatch):
    """serve.llm's programs for Granite-4.0-H-Small as the benchmark's
    configuration cuts it (``benchmarks/configs/granite-4.0-h-small-10l.json``):
    the published widths, layers 0-9 as one section of a whole period a call
    (five Mamba-2 layers, attention, four Mamba-2 layers), experts 0-35 of every
    layer's 72, rows 0-50,175 of the tied vocabulary, the engine's 48 slots over
    6,145 blocks of the one attention layer and 49 state rows of nine (128,
    8192) float32 states. The file's arithmetic against the compiler: 9.51 GB
    of weights, 1.88 GB of state rows and a 0.40 GB K/V pool are the programs'
    arguments, 11.80 GB, and the pool comes back in place. The decode step's
    body holds the state's update nine times (``selective_scan_update``: a 4 MB
    row in and out, twice buffered, 128 heads of 64 under decays a channel), the
    paged kernel once (it writes the step's row) and **all three** grouped
    matmuls of every layer in the grouped kernel: the step's 480 rows as a
    window of 512 under row tiles of 128 (an expert gets 6.7 rows: ``moe._row_tile``;
    two tiles hold the ~240 held rows), ``e_gate`` in two tiles of its
    contraction and ``e_down`` whole; no ``ragged-dot``, no conditional, no
    pool, state or expert stack copied. A prefill of 1,024 holds the flash
    kernel once (its softmax's scale the model's own), runs the recurrence as
    matrix products in ``ssd_prefill`` nine times (eight chunks of 128 whatever
    the published 256: no array of all heads' decays, 134 MB a layer at the
    parent, nor any (heads, N, P) state is left in the program) and hands every
    layer's 10,240 rows to the grouped kernel in one call, under row tiles of
    256 (142 rows an expert)."""
    import json
    import re

    from benchmarks.families import granite_hybrid as family
    from ray_tpu.models import granite_hybrid as M, paged
    from ray_tpu.serve.llm.deployment import _resolve_model_cfg

    _steered_to_tpu(monkeypatch)
    stated = _stated_tilings(monkeypatch)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs", "granite-4.0-h-small-10l.json")) as f:
        config = json.load(f)
    cfg = _resolve_model_cfg(family.model_kwargs(config))
    e = config["engine"]
    block, blocks, batch, per_seq = e["block_size"], e["num_blocks"], e["max_batch"], e["max_blocks_per_seq"]
    prefill, _, decode_greedy = paged.make_paged_fns(M.paged_layer, cfg, block_size=block, state_rows=True)
    params = _on(one_chip, jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    pool = _on(one_chip, jax.eval_shape(lambda: M.init_paged_pool(cfg, blocks, block, batch + 1)))

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    assert 9.51e9 < nbytes(params) < 9.52e9 and "unembed" not in params
    assert 0.40e9 < nbytes(pool["kv"]) == blocks * M.paged_block_bytes(cfg, block) < 0.41e9
    rows = sum(nbytes(pool[name]) for name in ("state", "conv", "state_pos"))
    assert 1.87e9 < rows == (batch + 1) * M.paged_state_bytes(cfg) < 1.89e9
    flat, state = f"{blocks * block * 8},128", "49,128,8192"
    assert pool["kv"].shape == (1, 2, blocks * block * 8, 128) and pool["state"].shape == (9, 49, 128, 8192)
    assert params["e_gate"].shape == (10, 36, 4096, 768) and params["e_down"].shape == (10, 36, 768, 4096)
    assert params["ssm_in"].shape == (9, 4096, 16768) and params["wqkv"].shape == (1, 4096, 6144)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def pools_copied(text):
        """Instructions of their own that make a pool, or a layer of one, anew."""
        pools = {f"{lead}{dims}" for dims in (flat, state, "49,33792") for lead in ("", "1,", "9,", "2,", "1,2,")}
        return [(dims, op) for dims, _, op in _alone(text)
                if dims in pools and op in ("copy", "transpose", "gather", "dynamic-slice")]

    def pool_writes(text):
        return re.findall(rf"= bf16\[1,2,{flat}\]\S* (?:dynamic-update-slice|scatter)\(", text)

    def state_writes(text):
        return re.findall(rf"= f32\[9,{state}\]\S* (?:dynamic-update-slice|scatter)\(", text)

    def gmm_rows(text):
        calls = [line for line in text.splitlines() if re.search(r"%gmm[.\d]* = ", line)]
        return sorted(int(re.search(r"= \w+\[(\d+),\d+\]", line).group(1)) for line in calls)

    compiled = decode_greedy.lower(
        params, arg((batch,), jnp.int32), arg((batch,), jnp.int32), arg((batch, per_seq), jnp.int32), pool,
        arg((batch,), jnp.bool_),
    ).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    kernels = _kernels(text)
    # the period's body holds its ten layers' kernels once: nine updates, one paged attention, three grouped matmuls a layer
    assert (kernels.count("selective_scan_update"), kernels.count("paged_decode_attention"), kernels.count("gmm")) == (9, 1, 30)
    assert gmm_rows(text) == [512] * 30 and "ragged-dot" not in text and " conditional(" not in text
    assert stated == {(128, 2048, 768), (128, 768, 4096)}  # gate and up; down: row tiles of 128 in the 480 rows' window of 512
    stated.clear()
    _nothing_is_copied_for_the_grouped_matmuls(text)
    assert not pools_copied(text) and not pool_writes(text) and not state_writes(text)
    assert "paged_scatter" not in text and "paged_gather" not in text
    assert 11.7e9 < mem.argument_size_in_bytes < 11.9e9 and mem.temp_size_in_bytes < 0.2e9
    assert mem.alias_size_in_bytes > 0.999 * nbytes(pool)  # the pool comes back in place
    compiled = prefill.lower(
        params, arg((1, 1024), jnp.int32), arg((1, per_seq), jnp.int32), pool, arg((), jnp.int32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    kernels = _kernels(text)
    assert (kernels.count("flash_attention"), kernels.count("gmm")) == (1, 30) and gmm_rows(text) == [10240] * 30
    assert kernels.count("ssd_prefill") == 9 and "selective_scan" not in " ".join(kernels) and "paged_decode_attention" not in kernels
    # the chunks' decays stay in fast memory and the state lies (N, heads x P) from the first chunk: no (.., 128 heads, 256, 256)
    # or (.., 128, 128, 128) array of decays and no state with its heads apart, in any order of their dimensions
    assert not re.search(r"f32\[(?:\d+,)*128,(?:256,256|128,128)\]", text) and not re.search(r"f32\[(?:\d+,)*128,128,64\]", text)
    assert stated == {(256, 2048, 768), (256, 768, 4096)}  # one call of every row: no window is walked
    _nothing_is_copied_for_the_grouped_matmuls(text, but_the_metadata=True)
    assert "ragged-dot" not in text
    assert len(pool_writes(text)) == 2 and "paged_scatter" in text  # a prompt's blocks, K and V, the one attention layer
    assert len(state_writes(text)) == 9  # the prompt's last state into its row, a Mamba layer
    assert not pools_copied(text)
    assert mem.temp_size_in_bytes < 1.0e9


def test_nemotron_h_decode_and_prefill_at_published_widths(one_chip, monkeypatch):
    """serve.llm's programs for Nemotron 3 Super as the benchmark's
    configuration cuts it (``benchmarks/configs/nemotron-3-super-120b-11l.json``):
    the published widths, layers 0-10 (``MEMEMEM*EME``) as one section of eleven
    layers a call, each a norm and one part, experts 0-127 of every expert
    layer's 512 in a 1,024-wide latent, rows 0-32,767 of the embedding and of
    the head, the engine's 48 slots over 1,585 blocks of 64 positions of the
    one attention layer and 49 state rows of five (128, 8192) float32 states.
    The file's arithmetic against the compiler: 9.30 GB of weights, 1.05 GB of
    state rows and a 0.10 GB K/V pool are the programs' arguments, 10.45 GB,
    and the pool comes back in place. The decode step's body holds the state's
    update five times (eight B/C groups of eight lane tiles each), the paged
    kernel once (two K/V heads: a block is 128 rows of 128) and **two** grouped
    matmuls an expert layer in the grouped kernel: the step's 1,056 rows as a
    window of 512 under row tiles of 128 (an expert gets 2.06 rows: ``moe._row_tile``;
    three tiles hold the ~264 held rows) over a stack of 640 groups, ``e_up``
    (1,024 x 2,688) whole and ``e_down`` in three tiles of its contraction, the
    hidden rows out of the first in float32 for the square; no ``ragged-dot``,
    no conditional, no pool, state or expert stack copied. A prefill of 1,024
    holds the flash kernel once, ``ssd_prefill`` five times and the same two
    grouped calls a layer inside the walk over eleven windows of 512, under two
    row tiles of 256 (44 rows an expert)."""
    import json
    import re

    from benchmarks.families import nemotron_h as family
    from ray_tpu.models import nemotron_h as M, paged
    from ray_tpu.serve.llm.deployment import _resolve_model_cfg

    _steered_to_tpu(monkeypatch)
    stated = _stated_tilings(monkeypatch)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs", "nemotron-3-super-120b-11l.json")) as f:
        config = json.load(f)
    cfg = _resolve_model_cfg(family.model_kwargs(config))
    e = config["engine"]
    block, blocks, batch, per_seq = e["block_size"], e["num_blocks"], e["max_batch"], e["max_blocks_per_seq"]
    prefill, _, decode_greedy = paged.make_paged_fns(M.paged_layer, cfg, block_size=block, state_rows=True)
    params = _on(one_chip, jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg)))
    pool = _on(one_chip, jax.eval_shape(lambda: M.init_paged_pool(cfg, blocks, block, batch + 1)))

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    assert 9.29e9 < nbytes(params) < 9.31e9 and params["unembed"].shape == (4096, 32768) and "e_gate" not in params
    assert 0.10e9 < nbytes(pool["kv"]) == blocks * M.paged_block_bytes(cfg, block) < 0.11e9
    rows = sum(nbytes(pool[name]) for name in ("state", "conv", "state_pos"))
    assert 1.04e9 < rows == (batch + 1) * M.paged_state_bytes(cfg) < 1.05e9
    flat, state = f"{blocks * block * 2},128", "49,128,8192"
    assert pool["kv"].shape == (1, 2, blocks * block * 2, 128) and pool["state"].shape == (5, 49, 128, 8192)
    assert params["e_up"].shape == (5, 128, 1024, 2688) and params["e_down"].shape == (5, 128, 2688, 1024)
    assert params["ssm_in"].shape == (5, 4096, 18560) and params["wqkv"].shape == (1, 4096, 4608) and params["norm"].shape == (11, 4096)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def pools_copied(text):
        """Instructions of their own that make a pool, or a layer of one, anew."""
        pools = {f"{lead}{dims}" for dims in (flat, state, "49,40960") for lead in ("", "1,", "5,", "2,", "1,2,")}
        return [(dims, op) for dims, _, op in _alone(text)
                if dims in pools and op in ("copy", "transpose", "gather", "dynamic-slice")]

    def pool_writes(text):
        return re.findall(rf"= bf16\[1,2,{flat}\]\S* (?:dynamic-update-slice|scatter)\(", text)

    def state_writes(text):
        return re.findall(rf"= f32\[5,{state}\]\S* (?:dynamic-update-slice|scatter)\(", text)

    def gmm_shapes(text):
        calls = [line for line in text.splitlines() if re.search(r"%gmm[.\d]* = ", line)]
        return sorted(re.search(r"= (\w+\[\d+,\d+\])", line).group(1) for line in calls)

    compiled = decode_greedy.lower(
        params, arg((batch,), jnp.int32), arg((batch,), jnp.int32), arg((batch, per_seq), jnp.int32), pool,
        arg((batch,), jnp.bool_),
    ).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    kernels = _kernels(text)
    # the body holds its eleven layers' kernels once: five updates, one paged attention, two grouped matmuls an expert layer
    assert (kernels.count("selective_scan_update"), kernels.count("paged_decode_attention"), kernels.count("gmm")) == (5, 1, 10)
    assert gmm_shapes(text) == ["f32[512,1024]"] * 5 + ["f32[512,2688]"] * 5  # up's hidden rows in float32, for the square
    assert "ragged-dot" not in text and " conditional(" not in text
    assert stated == {(128, 1024, 2688), (128, 896, 1024)}  # up whole; down: row tiles of 128 in the 1,056 rows' window of 512
    stated.clear()
    _nothing_is_copied_for_the_grouped_matmuls(text)
    assert not pools_copied(text) and not pool_writes(text) and not state_writes(text)
    assert "paged_scatter" not in text and "paged_gather" not in text
    assert 10.44e9 < mem.argument_size_in_bytes < 10.46e9 and mem.temp_size_in_bytes < 0.1e9
    assert mem.alias_size_in_bytes > 0.999 * nbytes(pool)  # the pool comes back in place
    compiled = prefill.lower(
        params, arg((1, 1024), jnp.int32), arg((1, per_seq), jnp.int32), pool, arg((), jnp.int32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    kernels = _kernels(text)
    assert (kernels.count("flash_attention"), kernels.count("gmm"), kernels.count("ssd_prefill")) == (1, 10, 5)
    assert gmm_shapes(text) == ["f32[512,1024]"] * 5 + ["f32[512,2688]"] * 5  # a window of the eleven a layer walks
    assert "selective_scan" not in " ".join(kernels) and "paged_decode_attention" not in kernels
    assert stated == {(256, 1024, 2688), (256, 896, 1024)}  # a prompt's windows keep the tile at the ridge
    _nothing_is_copied_for_the_grouped_matmuls(text, but_the_metadata=True)
    assert "ragged-dot" not in text
    assert len(pool_writes(text)) == 2 and "paged_scatter" in text  # a prompt's blocks, K and V, the one attention layer
    assert len(state_writes(text)) == 5  # the prompt's last state into its row, a mixer layer
    assert not pools_copied(text)
    assert mem.temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("s", [256, 512, 1024])
@pytest.mark.parametrize("heads, p, groups, n", [(128, 64, 1, 128), (32, 128, 2, 256)], ids=["granite", "falcon-h1"])
def test_the_prompts_ssd_kernel_at_the_published_head_shapes(one_chip, monkeypatch, heads, p, groups, n, s):
    """``ops/ssd.py`` alone at the two mixers the benchmark serves, a bucket a
    case: on a TPU the shapes choose the kernel, which compiles within the
    fast memory it states (``_VMEM_LIMIT``: Mosaic refuses a kernel over it),
    and beside ``x``, ``y`` and the state the program keeps under 8 MB (the
    chunks' ``C B^T``, ``dt`` and its running sums by block of heads): the
    heads' decays, 134 MB a layer at Granite's 1,024 before, are nowhere."""
    from ray_tpu.ops import ssd

    _steered_to_tpu(monkeypatch)
    assert ssd.can_use_ssd_kernel(s, heads, p, groups, n) and ssd._VMEM_LIMIT <= 32 << 20
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)  # noqa: E731
    compiled = jax.jit(ssd.ssd_chunked).lower(
        f32(1, s, heads, p), f32(1, s, heads), f32(heads), f32(1, s, groups, n), f32(1, s, groups, n)
    ).compile()
    assert _kernels(compiled.as_text()) == ["ssd_prefill"]
    assert compiled.memory_analysis().temp_size_in_bytes < 8e6


def _steered_to_tpu(monkeypatch):
    """``attention`` asks ``jax.default_backend()``, which is the CPU here:
    the test steers it to the branch it takes on the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _bench_cfg(n_layers):
    from ray_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        n_layers=n_layers, remat_policy="dots", **{**GPTJ, "vocab_size": 50432}
    )


def _bench_step(topo, n_layers):
    """``build_lm_train_step``'s ``jit_step`` lowered at ``bench.py``'s
    geometry (GPT-J widths, batch 8 x 2048, dots remat) on one described chip."""
    from ray_tpu.parallel.spmd import build_lm_train_step

    mesh = Mesh([topo.devices[0]], ("data",))
    bundle = build_lm_train_step(_bench_cfg(n_layers), mesh, learning_rate=1e-4)
    state = jax.eval_shape(bundle.init_fn, jax.random.PRNGKey(0))
    rep = NamedSharding(mesh, PartitionSpec())
    tok = jax.ShapeDtypeStruct((8, 2048), jnp.int32, sharding=bundle.batch_shard)
    return bundle.step_fn.lower(_on(rep, state), tok, tok)


@pytest.fixture(scope="module")
def bench_step(topo):
    """The benchmark's training step (4 layers) compiled once for the tests
    that read it."""
    with pytest.MonkeyPatch.context() as monkeypatch:
        _steered_to_tpu(monkeypatch)
        return _bench_step(topo, 4).compile()


def test_train_step_at_bench_geometry(bench_step):
    """``build_lm_train_step`` at ``bench.py``'s geometry (GPT-J widths,
    4 layers, batch 8 x 2048, dots remat) on one described chip."""
    compiled = bench_step
    # a forward kernel a layer, in the forward pass alone (the backward pass
    # takes its output and sums from what "dots" kept), and a backward
    # kernel a layer. benchmarks/layer_metrics/flash_attn_roofline.py finds them by
    # name: KERNELS = ("flash_attention", "flash_mha")
    text = compiled.as_text()
    kernels = _kernels(text)
    assert kernels == ["flash_attention_fwd"] * 4 + ["flash_mha_bwd"] * 4
    assert all("flash_attention" in k or "flash_mha" in k for k in kernels)
    # the step fits without the compiler's own rematerialization pass: short of
    # room it recomputes what it judges cheapest and names it `<op>.remat`
    # (PR 40's first tree: the head's logits and two projections' backward
    # products, 60 ms of a 750 ms step, and no other sign of it)
    import re

    assert not re.findall(r"%[\w.\-]+\.remat[\d.]* = ", text)
    # the chip's 15.75 GiB hold the arguments (the state, aliased to the
    # results) and the step's own with 1 GB and more to spare
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0.99 * mem.argument_size_in_bytes
    # temp_size counts the aliased arguments (7.31 GB) with the step's own
    assert mem.argument_size_in_bytes < mem.temp_size_in_bytes < 15.0e9


def _moved_alone(text):
    """(dims, name) of every instruction that runs by itself and computes
    nothing: a ``copy``, a ``slice``, a ``dynamic-slice``, or a fusion of
    nothing but those (``dynamic-slice_bitcast_fusion``: a slice copied out
    of what it is cut from before its reader runs)."""
    import re

    moves = {"parameter", "constant", "bitcast", "copy", "slice", "dynamic-slice", "reshape", "transpose"}
    bodies, fused = _computations(text)

    def computes_nothing(op, rest):
        if op != "fusion":
            return op in ("copy", "slice", "dynamic-slice")
        return {o for *_, o, _ in bodies[re.search(r"calls=%([\w.\-]+)", rest).group(1)]} <= moves

    return [
        (dims, name) for comp, body in bodies.items() if comp not in fused
        for name, dims, _, op, rest in body if computes_nothing(op, rest)
    ]


def test_train_step_reads_a_layers_saved_outputs_where_they_were_written(bench_step):
    """The same step: no instruction that runs by itself copies a layer's
    saved tensor, or a slice of a saved stack that size, before a kernel or a
    fusion reads it. With the layers' loop rolled its backward body held eight
    (PERF.md, PR 62): six slices of the saved stacks at a traced index, and
    the kernel's output and dO re-laid."""
    cfg = _bench_cfg(4)
    saved = [(8, 2048, cfg.d_model), (8, 2048, cfg.n_heads, cfg.head_dim), (8, 2048, cfg.d_ff)]
    saved = {lead + ",".join(map(str, dims)) for dims in saved for lead in ("", "1,")}
    text = bench_step.as_text()
    assert " while(" not in text
    assert not [(dims, name) for dims, name in _moved_alone(text) if dims in saved]


@pytest.mark.parametrize("n_layers,loops", [(8, 0), (9, 2), (28, 2)])
def test_train_steps_loop_is_rolled_past_a_depth(topo, monkeypatch, n_layers, loops):
    """``transformer.UNROLLED_LAYERS``: up to that depth every layer stands
    in the lowered step; a deeper model (GPT-J-6B's 28) keeps one rolled loop
    forward and one back, and their one compiled body each."""
    from ray_tpu.models.transformer import UNROLLED_LAYERS

    _steered_to_tpu(monkeypatch)
    assert (n_layers <= UNROLLED_LAYERS) == (loops == 0)
    assert _bench_step(topo, n_layers).as_text().count("stablehlo.while") == loops


def test_train_step_on_a_2x2_mesh(topo, monkeypatch):
    """The same step over ``MeshConfig(fsdp=2, tensor=2)`` on the described
    topology's four chips (what ``chip_smoke.py --chips 4`` runs). GSPMD
    cannot partition the flash kernel: this is the compile that refused the
    step until attention ran per shard. The state leaves ``init`` sharded the
    way the step returns it (aliased whole), with collectives in between."""
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.spmd import build_lm_train_step

    _steered_to_tpu(monkeypatch)
    mesh = create_mesh(MeshConfig(fsdp=2, tensor=2), devices=topo.devices)
    bundle = build_lm_train_step(_bench_cfg(2), mesh, learning_rate=1e-4)
    init = bundle.init_seed_fn.lower(0).compile()
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(bundle.init_fn, jax.random.PRNGKey(0)),
        init.output_shardings,
    )
    tok = jax.ShapeDtypeStruct((8, 2048), jnp.int32, sharding=bundle.batch_shard)
    step = bundle.step_fn.lower(state, tok, tok).compile()
    text = step.as_text()
    # the named residuals are found inside the shard_map: one forward kernel,
    # in the forward loop's body (a mesh's step keeps its layers' loop rolled)
    assert _kernels(text) == ["flash_attention_fwd", "flash_mha_bwd"]
    assert text.count(" while(") == 2
    assert "all-gather" in text and "all-reduce" in text
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes > 0.99 * mem.argument_size_in_bytes  # all but the batch
