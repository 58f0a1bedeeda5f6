"""What a served decoder program is, apart from its layers
(docs/serving.md, "The decoder program"): `DecoderProgram`, the base of
every family's program (`gpt.py`, `mimo_v2.py`, `keye_vl2.py`,
`kimi_k2.py`, `ouro.py`, `cohere2_moe.py`, `jamba.py`).

A family states

- ``signature``, in its constructor: what a reloaded model must share
  beyond its shapes;
- ``cache_shapes(B)``: its cache, as data: the stacks that grow by a
  position a token, optionally the **states** that hold one block a
  (layer, row) and are replaced whole each step (a state-space layer's
  state, its convolution's tail), and the counters;
- ``body(ctx, w, cache, toks)``: its layers and its head on one block,
  traced;
- ``counters(cache)`` where it counts in the donated carry.

The base gives the rest of the contract `serving.ServingEngine` sees:
``weights()``, ``init_cache(B)``, ``step(..)``, ``window``, ``vocab`` and
the five tallies.  What every family decides alike is decided here and
in `ops/cache_write.py` and `ops/ssm.py`, once: which rows of a decode
step still count (``live``), what a row's write and a row's state are
told of them (a row that is not live keeps its positions, its state and
its tail as they were), that a written stack or state stays in the
layout its donated buffer came in, and what is counted while a step is
traced.
"""

from __future__ import annotations

from ...ops import cache_attention, cache_write, ssm


def own_weights(model, dtype):
    """A program's weight tuple: the parameters' own buffers, in the
    order of their names: no second copy, unless ``dtype`` asks for
    another type than a parameter has."""
    out = []
    for n in model._names:
        a = getattr(model, n).data()._data
        if dtype is not None and a.dtype != dtype:
            a = a.astype(dtype)
        out.append(a)
    return tuple(out)


class Step:
    """One traced step's context, handed to a family's ``body``:

    - ``B``, ``S`` the block's rows and positions, ``decode`` (S == 1);
    - ``pos`` (B,) each row's first position, ``last`` (B,) the index in
      the block of each row's last real token;
    - ``live`` (B,) bool: the rows that still want a token (all of them
      where the step was handed none: a prefill, a host walk);
    - ``held`` (B,) int32: a decode step's positions a row, itself
      included, none for a row that is not live: the lengths attention
      over the cache reads to, and what a family's decode counters sum;
    - ``writes``, ``reads``, ``attends``: this trace's tallies (the
      program's ``cache_writes[S]``, ``cache_reads[S]``,
      ``block_attends[S]``): the row writes by path, the attention calls
      over the cache by path, and the attention calls inside the block,
      which a family's body counts itself (``attends["kernel"] += 1``);
      ``updates`` (``state_updates[S]``): the rows whose state a scan
      or an update moved on, by path; ``products``
      (``grouped_products[S]``): the held experts' calls
      (`ops/moe.py::held_experts_ffn`, which a family hands it), by the
      path their grouped products took (``"kernel"``, ``"plain"``) and
      by the one their passes took into the stream
      (``"combine_kernel"``, ``"combine_plain"``).

    The states' recurrence is `ops/ssm.py`'s Mamba-1 pair (a decay a
    state element, a state ``(N, E)``) unless the family hands `scan`
    and `update` another pair's functions (``rows=``: Mamba-2's, a decay
    a head, a state ``(H P, N)``).  Both keep the same contract: a
    block from an empty state to each row's length, one position in
    place told ``live``.

    A family does four things with ``live`` in its decode branch and no
    fifth: lengths from ``held``, ``valid=live[:, None]`` to the
    experts, writes through ``write``, states through ``update`` and
    ``conv``.
    """

    def __init__(self, program, pos, last, toks, live, given):
        import collections

        import jax.numpy as jnp

        self.B, self.S = toks.shape
        self.decode = self.S == 1
        self.pos, self.last, self.live = pos, last, live
        self.held = jnp.where(live, pos + 1, 0)
        self.writes = program.cache_writes[self.S] = collections.Counter()
        self.reads = program.cache_reads[self.S] = collections.Counter()
        self.attends = program.block_attends[self.S] = collections.Counter()
        self.updates = program.state_updates[self.S] = collections.Counter()
        self.products = program.grouped_products[self.S] = \
            collections.Counter()
        self._given = given
        self._mesh = program._mesh
        self._layouts = program._layouts

    def _layouts_of(self, first, n):
        return None if self._layouts is None \
            else self._layouts[first:first + n]

    def write(self, stacks, news, l, starts, row=None, first=0):
        """Row r's new block ``news[i][r]`` (K, D, S') into
        ``stacks[i]`` at ``[l, row + r, :, :, starts[r]:]``
        (`cache_write.write_rows`): a decode step's write is told the
        rows that are live as the step was handed them, a prefill's
        none.  ``first``: the place in the cache of the first of these
        stacks (each comes back in the layout `init_cache` read for its
        place).  The named scope around the call, and around what turns
        the new rows for it, is the caller's, ``serve.cache_write``."""
        return cache_write.write_rows(
            stacks, news, l, starts, mesh=self._mesh, tally=self.writes,
            row=row, live=self._given,
            pins=self._layouts_of(first, len(stacks)))

    def write_ring(self, stacks, news, l, lengths, row=None, first=0):
        """The rings a prefilled block of ``lengths`` positions a row
        leaves (`cache_write.write_ring`); ``row``, ``first`` and the
        scope as `write`."""
        return cache_write.write_ring(
            stacks, news, l, lengths, tally=self.writes, row=row,
            pins=self._layouts_of(first, len(stacks)))

    def attend(self, q, ck, cv, l, lengths=None, mask=None, sink=None,
               leading=None):
        """A decode step's attention over layer ``l`` of the stacks
        (`cache_attention.attend_rows`), each row to ``held`` positions,
        or to ``lengths`` (B,), made of ``held`` and so already under
        ``live`` (a ring's ``min(held, window)``).  The named scope
        around the call is the caller's: its name is the family's
        metric."""
        return cache_attention.attend_rows(
            q, ck, cv, l, self.held if lengths is None else lengths,
            mask=mask, sink=sink, mesh=self._mesh, tally=self.reads,
            leading=leading)

    # -- states: one block a (layer, row), replaced whole ---------------------

    def _put(self, states, block, l, row, first):
        """A block's rows ``block`` (R, ...) into ``states[l, row:]``,
        the result in the layout `init_cache` read for place
        ``first``."""
        import jax.numpy as jnp
        from jax import lax

        at = (jnp.int32(l), jnp.int32(0 if row is None else row)) \
            + (jnp.int32(0),) * (states.ndim - 2)
        return self._pin(lax.dynamic_update_slice(
            states, block.astype(states.dtype)[None], at), first)

    def _pin(self, states, first):
        return cache_write._pinned((states,), self._layouts_of(first, 1))[0]

    def scan(self, states, l, c, dt, A, B, C, D, lengths, row=None,
             first=0, rows=ssm.selective_scan_rows):
        """A prefilled block through layer ``l``'s recurrence from an
        empty state (``rows``, which says what its operands are), each
        row to ``lengths`` (B,): returns (y, the states ``(L, B, ...)``
        with rows ``row ..`` of layer ``l`` what each row's last real
        position left).  ``row``, ``first`` and the scope as `write`."""
        y, h = rows(c, dt, A, B, C, D, lengths, tally=self.updates)
        return y, self._put(states, h, l, row, first)

    def update(self, states, l, c, dt, A, B, C, D, first=0,
               rows=ssm.state_update_rows):
        """A decode step's one position a row (``rows``), in place on
        the donated states and told the rows that are live as the step
        was handed them: another row's state is not moved.  Returns (y,
        zero for a row that is not live; the states)."""
        y, states = rows(states, l, c, dt, A, B, C, D, live=self._given,
                         tally=self.updates)
        return y, self._pin(states, first)

    def conv(self, tails, l, a, w, b, lengths=None, row=None, first=0):
        """The causal depthwise convolution before the recurrence, and
        the tail it leaves in ``tails`` ``(L, B, (k - 1) E)``: a block
        ``a`` (R, S, E) from no history, each row to ``lengths``
        (`ssm.causal_conv_rows`), or a decode step's one position from
        the tails (`ssm.conv_step`), where the tail of a row that is
        not live comes back as it was.  Returns (c, the tails)."""
        if self.decode:
            c, tails = ssm.conv_step(tails, l, a[:, 0], w, b,
                                     live=self._given)
            return c[:, None], self._pin(tails, first)
        c, tail = ssm.causal_conv_rows(a, w, b, lengths)
        return c, self._put(tails, tail, l, row, first)


class DecoderProgram:
    """A family's decoder program for `serving.ServingEngine`:
    ``weights()``, ``init_cache(B)``, ``step(w, cache, pos, last, toks,
    live=None)``, of which a family writes ``cache_shapes`` and
    ``body``."""

    def __init__(self, model, dtype=None, mesh=None, tp_axis="tp"):
        self._model = model
        self._z = getattr(model, "_sizes", None)
        self._dtype = dtype
        self._mesh, self._tp_axis = mesh, tp_axis
        self.window = model._max_length
        self.vocab = model._vocab
        # the formats the cache's stacks were allocated in, one a stack
        # in the cache's order: `init_cache` reads them, once
        self._layouts = None
        # by block length S, told while the block-S step is traced:
        # cache_writes[S] its row writes, by path; cache_reads[S] its
        # attention calls over the caches, by path; block_attends[S]
        # its attention calls inside the block, by path;
        # state_updates[S] the rows whose state it moved on, by path;
        # grouped_products[S] its held experts' calls, by the paths of
        # their products and of their way out
        self.cache_writes = {}
        self.cache_reads = {}
        self.block_attends = {}
        self.state_updates = {}
        self.grouped_products = {}

    # -- what a family states --------------------------------------------------

    def cache_shapes(self, B):
        """``(stacks, counters)`` or ``(stacks, states, counters)``:
        lists of ``(shape, dtype)``, the cache of batch bucket B in its
        order: the stacks ``(L, B, K, D, W)`` first (dtype None: the
        serving type), then the states ``(L, B, ...)``, one block a
        (layer, row) that a step replaces whole (zero in a fresh cache,
        which is the start of a sequence), and then what rides in the
        same donated carry."""
        raise NotImplementedError

    def body(self, ctx, w, cache, toks):
        """The block ``toks`` (B, S) through the layers and the head:
        ``(cache, logits (B, vocab) float32 at ctx.last)``.  ``ctx``: a
        `Step`; ``w``: the weights by name; ``cache`` donated."""
        raise NotImplementedError

    # -- weights ---------------------------------------------------------------

    def weights(self):
        return own_weights(self._model, self._dtype)

    def _named(self, w):
        """The weight tuple as ``body`` reads it."""
        return dict(zip(self._model._names, w))

    def _embedding(self):
        """The array the caches lie beside and take their type from
        where ``dtype`` names none."""
        return self._model.embed_weight.data()._data

    # -- cache -----------------------------------------------------------------

    def _cache_sharding(self):
        """Where the stacks lie: beside the embedding, or sharded on
        their head axis over the mesh."""
        if self._mesh is None:
            return self._embedding().sharding
        from ...parallel.sharding import serving_cache_sharding

        return serving_cache_sharding(self._mesh, tp_axis=self._tp_axis)

    def init_cache(self, B):
        """The family's cache for batch bucket B, zeroed: the stacks,
        the states, then the counters.  Committed next to the weights
        (the engine serves from the device(s) the model was placed on,
        never from the process default), the stacks head-sharded under
        a mesh; states lie beside the embedding (a family that has them
        serves from one chip)."""
        import jax.numpy as jnp

        emb = self._embedding()
        stacks, *states, counters = self.cache_shapes(B)
        where = self._cache_sharding()
        kv_dtype = self._dtype or emb.dtype
        stacks = tuple(jnp.zeros(shape, dtype or kv_dtype, device=where)
                       for shape, dtype in stacks) + tuple(
            jnp.zeros(shape, dtype or kv_dtype, device=emb.sharding)
            for shape, dtype in (states[0] if states else ()))
        if self._layouts is None:
            # how this platform lays a stack out on the device: read off
            # an allocated one, not assumed
            self._layouts = cache_write.layouts_of(stacks)
        return stacks + tuple(
            jnp.zeros(shape, dtype, device=emb.sharding)
            for shape, dtype in counters)

    # -- the traced step -------------------------------------------------------

    def step(self, w, cache, pos, last, toks, live=None):
        """cache donated; pos (B,) each row's first position; last (B,)
        the index in the block of each row's last real token; toks
        (B, S).  Returns (cache, logits (B, vocab) float32 at ``last``).
        S > 1 is a prefill: it is handed no ``live``.  S = 1 is a decode
        step; there ``live`` (B,) bool marks the rows that still want a
        token (None: all): another row attends to nothing, goes to no
        expert, is counted nowhere and leaves the cache as it was, its
        state and its tail too."""
        import jax.numpy as jnp

        given = live    # as handed: None from the prefill, whose write takes none
        if live is None:
            live = jnp.ones((toks.shape[0],), bool)
        return self.body(Step(self, pos, last, toks, live, given),
                         self._named(w), cache, toks)
