"""The paged program: what every model kind ``serve.llm`` runs has in common.

The engine keeps one device-wide pool of fixed-size blocks and, for each
sequence, a block table mapping absolute positions to pool blocks. Shapes stay
static (tables are dense int32 arrays padded with the reserved null block 0),
so one compiled decode step serves whichever sequences occupy the batch slots.
This module owns what no kind restates: the embedding lookup, where a call's
cache rows go (``Step``), the scan over layers with the pool in its carry, the
head, and the three jitted programs (``make_paged_fns``). To it the pool is an
opaque pytree that a kind's layer maps to a new one.

A model kind is one entry of ``models.PAGED_KINDS`` and one module that gives
four things, and may give a fifth:

    paged_layer(cfg, params, step) -> layer(x (B, S, D), pool, li) -> (x, pool)
        or, for a model of unlike layers, its sections in order:
        [(layer, how many layers it covers), ...]; a section whose body is
        several layers a call: (layer, how many it covers, how many a call);
        one that a prefill runs on its last position alone: (layer, how many
        it covers, how many a call, True); and, for a kind whose layers hand
        on a value that is not the residual, ``Carried(sections, leaf)``
    init_paged_pool(cfg, num_blocks, block_size) -> pool
    paged_block_bytes(cfg, block_size) -> bytes one block holds over all layers
    init_params(key, cfg) -> params with ``embed``, ``final_norm``, ``unembed``
        (or none: the embedding is tied)
    paged_layouts(cfg) -> {name of a stacked tensor: its ``major_to_minor`` on
        the device}, for the tensors whose default layout the layer reads badly

over a config with ``n_layers`` and ``max_seq_len`` (and ``rms_norm_eps``,
where the final norm's is not ``rms_norm``'s own; or ``final_norm(params, x)``,
where the final norm is not an RMSNorm at all). **Two parts a kind may build
from**, each the one owner of a format in the pool: ``models/mamba2.py`` (the
Mamba-2 mixer, its state rows and their bytes) and ``models/flat_kv.py`` (the
flat K/V pool ``"kv"``: its shape, a block's bytes, how a call's rows are
written and read back, which kernel scores them). A kind with plain softmax
attention over K/V rows keeps its projection, its position signal and ``wo``,
calls ``flat_kv.attend`` between them and writes no pool code of its own; its
``init_paged_pool`` and ``paged_block_bytes`` call ``flat_kv.init_pool`` and
``flat_kv.block_bytes`` for that part. ``at(params, index)`` is how any kind
reads a layer's tensors (below: how a layer gets its weights). **A model of unlike
layers** (Kimi-K2: one dense layer, then expert layers) hands back a section
a kind of layer, and ``forward_paged`` runs one scan a section with ``(x,
pool)`` carried from each into the next and the layer index running on
(section two of a 1 + 6 model sees ``li`` 1..6: the pool's rows of layer
``li`` are every section's, a section's own stacked tensors it indexes from
its own first layer). How the sections divide the layers is the config's
(``first_k_dense_replace``); the sum must be ``cfg.n_layers``. A kind that
hands back one function is one section of ``cfg.n_layers``: the program it
always traced. **A model whose layers alternate in a period** (Olmo-Hybrid:
three linear-attention layers and one of full attention, eight times) hands
back one section whose body is a whole period, ``(layer, n_layers, layers a
call)``: one scan over the periods, ``li`` each period's first layer; sixteen
sections of a layer each would be sixteen scans. **A kind that keeps a state a
sequence** beside its rows a position (``paged_state_bytes(cfg)``: the
recurrent layers' state) finds each sequence's state row in the block table's
last column: the programs made with ``state_rows=True`` cut it off the table
and hand it to the kind as ``Step.state_rows`` (row 0 is the null row, as
block 0 is the null block: an inactive slot's all-zero table names both).
**A kind whose layers hand on a value beside the residual**
(a state-space layer's scan output that later layers gate: ``models/phi4flash.py``)
hands back ``Carried(sections, leaf)``: the leaf (B, S, ...) rides in the
scans' carry after ``x`` and the pool, and the kind's layers take and return
it, ``layer(x, pool, leaf, li) -> (x, pool, leaf)``. Every other kind's carry
is ``(x, pool)`` as it always was. **A section that a prefill runs on its
last position alone** (layers whose every input a decode step could give them:
attention over a cache that an earlier section wrote, a gate on the carried
leaf) says so with a fourth entry: ahead of its scan a prefill cuts ``x`` and
the leaf down to position ``last`` and asks the kind for its sections once more
over a ``Step`` of that one position (``length`` cached positions, the
position's own slot), which is a decode step's; the head then norms the one
position it is left with. A decode step is untouched.
``paged_layer`` is called
once a program (twice in a prefill with such a section), outside the scan over layers, and what it computes there is
computed once a call: XLA does not lift it out of the loop by itself (a rotary
table built inside the layer was rebuilt 28 times a step: PERF.md section 6, PR
31). **How a layer gets its weights is decided here:** ``params`` reaches the
kind whole and the scan carries only the layer's index, so a matmul reads its
matrix out of the stacked tensor through one dynamic index. Handed to the scan
as per-layer inputs, a layer's tensors are copied out of the stack before use:
every weight read and written once more a step (30 of 47 ms, PERF.md section 6,
PR 29). **How a weight lies on the device is decided here too**
(``place_params``): shape, key and values are the kind's and training's, the
order of the dimensions in memory is the paged programs'. The device tiles an
array's two minor dimensions, and a matmul reads its matrix in place only where
the contraction lies in those tiles. A tensor that has it further out (GPT-J's
``wq``: (L, D, H, Hd), contracted over D) is staged whole in fast memory before
its dot, every layer of every step: a q/k/v projection cost 2.9 ms a step where
``wo``, the same bytes, costs 1.3 (PERF.md section 6, PR 32). The kind names such
tensors and the engine places them once, before the pool exists; the programs
are plain ``jax.jit``s, which take a committed argument's layout as it comes.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache
from jax.experimental.layout import Format, Layout

from ray_tpu.ops.layers import rms_norm

UNSTACKED = ("embed", "unembed", "final_norm")  # the program's own: the lookup and the head


class Step(NamedTuple):
    """Where one call's tokens are and where their cache rows go: the same for
    every layer. A slot is a row of the pool seen flat, block ``b`` covering
    slots ``[b * block_size, (b + 1) * block_size)``. Block 0 is the null
    block: padded table entries and masked rows' writes land there, and what it
    holds is always behind the mask, so attention never reads it."""

    positions: jax.Array  # (B, S) absolute
    block_tables: jax.Array  # (B, MB): a sequence's block index -> pool block
    block_size: int
    write_slots: jax.Array  # (B * S,): each token's slot; a masked row's lies in the null block
    live: jax.Array  # (B * S,) bool: the rows that are tokens
    lengths: jax.Array  # (B,): a decode step's sequences count positions [0, position]; an inactive slot none
    state_rows: Optional[jax.Array] = None  # (B,): each sequence's state row, for a kind that keeps one; 0 the null row


def at(params, index):
    """``w(name)``: layer ``index``'s tensor of the stack ``params[name]``, read
    out of it in place by one dynamic index (module docstring: how a layer gets
    its weights). ``index`` counts the stack's own layers."""
    return lambda name: jax.lax.dynamic_index_in_dim(params[name], index, keepdims=False)


class Carried(NamedTuple):
    """A kind's sections with a leaf of its own in the scans' carry (module
    docstring): ``leaf`` (B, S, ...) as the first section finds it."""

    sections: list
    leaf: Any


@contextlib.contextmanager
def _no_compile_cache():
    """Inside, a program is compiled and never read from JAX's persistent
    compile cache. **A program whose result has a layout of its own must not
    come from that cache** (JAX 0.9.0, the CPU backend and the chip alike): the
    executable read back has forgotten its result's layout, so the array comes
    out labelled with the default one over bytes that lie heads-major, and
    every later reader gets other weights, silently (PERF.md section 6, PR 32).
    Layouts of a program's *arguments* survive the cache, so the three paged
    programs are cached as ever."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()  # the cache's verdict on itself is remembered
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def place_params(model, cfg, params):
    """``params`` with each tensor that the kind's module names
    (``model.paged_layouts(cfg)``) re-laid on the device in its
    ``major_to_minor``, on the sharding it has; and what was placed, ``{name:
    major_to_minor}``. Chosen from the platform alone, as
    ``can_use_paged_kernel`` is: on a TPU it places; elsewhere (the CPU backend
    tiles nothing) and for a kind that names nothing it returns ``params``
    itself and ``{}``.

    One tensor at a time, and **the original is deleted** before the next is
    placed: the caller hands its parameters over. GPT-J-6B's 12.1 GB leave room
    for one 0.94 GB tensor in flight and, after this, for the pool; they do not
    leave room for both copies of all three. The copying program is compiled
    here every time (a second of a replica's start), and a tensor that does
    not come out in the layout asked for raises."""
    layouts = model.paged_layouts(cfg) if hasattr(model, "paged_layouts") else {}
    if not layouts or jax.default_backend() != "tpu":
        return params, {}
    layouts = {name: tuple(order) for name, order in layouts.items()}
    params = dict(params)
    with _no_compile_cache():
        for name, order in layouts.items():
            old = params[name]
            new = jax.block_until_ready(jax.device_put(old, Format(Layout(major_to_minor=order), old.sharding)))
            if new.format.layout.major_to_minor != order:
                raise RuntimeError(f"{name} asked for in major_to_minor {order} came out as {new.format.layout}")
            params[name] = new
            old.delete()
    return params, layouts


def head(cfg, params, x, last=None):
    """``x`` (B, S, D) -> float32 logits (B, S, V), or (B, 1, V) of position
    ``last`` alone (a prefill wants one position's, and S x V is not small)."""
    with jax.named_scope("head"):
        if last is not None:
            x = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
        norm = getattr(cfg, "final_norm", None)  # the kind's, where it is not an RMSNorm
        x = norm(params, x) if norm else rms_norm(x, params["final_norm"], getattr(cfg, "rms_norm_eps", 1e-6))
        unembed = params.get("unembed")
        if unembed is None:
            unembed = params["embed"].T
        return jnp.einsum("bsd,dv->bsv", x, unembed).astype(jnp.float32)


def forward_paged(paged_layer, cfg, params, tokens, positions, write_mask, block_tables, pool, block_size: int, last=None,
                  state_rows: bool = False):
    """``tokens`` (B, S) at per-sequence absolute ``positions`` (B, S) through
    ``cfg.n_layers`` calls of a kind's layer (or of its sections' layers, a
    scan a section), each writing its cache rows into the
    pool and attending over the sequences' blocks. ``write_mask`` (B, S)
    diverts padded rows and inactive slots to the null block. With
    ``state_rows`` the tables' last column is each sequence's state row
    (``Step.state_rows``), not a block. Returns (``head``'s logits, pool)."""
    b, s = tokens.shape
    rows = None
    if state_rows:
        block_tables, rows = block_tables[:, :-1], block_tables[:, -1]

    def sections_over(positions, write_mask):
        """The kind's sections, each (layer, layers covered, layers a call,
        last position only), and its carried leaf (or none), over a ``Step``
        of these positions."""
        n = positions.shape[1]
        pidx = jnp.clip(positions // block_size, 0, block_tables.shape[1] - 1)
        slot = jnp.take_along_axis(block_tables, pidx, axis=1) * block_size + positions % block_size
        null_slot = jnp.arange(b * n, dtype=slot.dtype) % block_size
        live = write_mask.reshape(-1)
        sections = paged_layer(cfg, params, Step(
            positions, block_tables, block_size, jnp.where(live, slot.reshape(-1), null_slot), live,
            jnp.where(write_mask[:, 0], positions[:, 0] + 1, 0), rows))
        leaf = ()
        if isinstance(sections, Carried):
            sections, leaf = sections.sections, (sections.leaf,)
        if callable(sections):
            sections = [(sections, cfg.n_layers)]
        return [(*section, *(1, False)[len(section) - 2:]) for section in sections], leaf

    sections, leaf = sections_over(positions, write_mask)
    if sum(n for _, n, _, _ in sections) != cfg.n_layers or any(n % each for _, n, each, _ in sections):
        raise ValueError(f"the sections cover {[n for _, n, _, _ in sections]} layers of {cfg.n_layers}, "
                         f"{[each for _, _, each, _ in sections]} a call")
    cut = last is not None and s > 1 and any(only for *_, only in sections)
    if cut:
        if not all(only for *_, only in sections[next(i for i, sec in enumerate(sections) if sec[3]):]):
            raise ValueError("a section on the last position alone is followed by one over every position")
        at = lambda t: jax.lax.dynamic_slice_in_dim(t, last, 1, axis=1)  # noqa: E731
        tail, _ = sections_over(at(positions), at(write_mask))

    # The pool rides in the scan CARRY, not in per-layer outputs: stacked scan
    # outputs allocate a fresh slab and copy every layer's rows through it,
    # which defeats buffer donation and turns each decode step into an
    # O(pool-size) memcpy. Carry-threaded updates alias in place.
    carry, first, narrowed = (params["embed"][tokens], pool, *leaf), 0, False
    for i, (layer, n, each, only) in enumerate(sections):
        if cut and only:
            if not narrowed:  # x and the kind's leaf down to the one position; the pool whole
                carry, narrowed = (at(carry[0]), carry[1], *(at(t) for t in carry[2:])), True
            layer = tail[i][0]
        # each call's first layer (a layer a call: the arange every kind has always traced)
        firsts = jnp.arange(first, first + n) if each == 1 else jnp.arange(first, first + n, each)
        carry, _ = jax.lax.scan(lambda c, li, layer=layer: (layer(*c, li), None), carry, firsts)
        first += n
    x, pool = carry[:2]
    return head(cfg, params, x, None if narrowed else last), pool


def make_paged_fns(paged_layer, cfg, *, block_size: int, state_rows: bool = False):
    """(prefill, decode_step, decode_step_greedy) over a kind's ``paged_layer``,
    jitted with the pool donated (in place on the device between steps).
    ``state_rows``: the kind keeps a state a sequence, and MB counts the
    tables' last column, which names each sequence's state row.

    prefill(params, tokens (1,S), block_table (1,MB), pool, length ())
        -> (logits at position length-1 (1,V), pool)
    decode_step(params, tokens (B,), positions (B,), block_tables (B,MB),
        pool, active (B,) bool) -> (logits (B,V), pool)
    decode_step_greedy(same args) -> (next tokens (B,) int32, pool)
        — argmax fused on device so a greedy batch ships B ints to the
        host per step instead of B x vocab logits (the hot serving path;
        identical tokens to argmax over ``decode_step``'s logits).

    Shapes are static per (S, MB, B): the engine buckets prompt lengths
    and runs decode at a fixed max batch, so each compiles exactly once.
    """

    @functools.partial(jax.jit, donate_argnums=(3,))
    def prefill(params, tokens, block_table, pool, length):
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None, :], tokens.shape)
        logits, pool = forward_paged(paged_layer, cfg, params, tokens, positions, positions < length, block_table, pool,
                                     block_size, last=length - 1, state_rows=state_rows)
        return logits[:, 0, :], pool

    def step(params, tokens, positions, block_tables, pool, active):
        logits, pool = forward_paged(paged_layer, cfg, params, tokens[:, None], positions[:, None], active[:, None],
                                     block_tables, pool, block_size, state_rows=state_rows)
        return logits[:, 0, :], pool

    @functools.partial(jax.jit, donate_argnums=(4,))
    def decode_step(params, tokens, positions, block_tables, pool, active):
        return step(params, tokens, positions, block_tables, pool, active)

    @functools.partial(jax.jit, donate_argnums=(4,))
    def decode_step_greedy(params, tokens, positions, block_tables, pool, active):
        logits, pool = step(params, tokens, positions, block_tables, pool, active)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pool

    return prefill, decode_step, decode_step_greedy
