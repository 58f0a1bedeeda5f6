"""Parameter sharding rules (tensor parallelism + FSDP).

NEW, TPU-first (SURVEY.md §2.5: TP is absent in the reference).  A rule set
maps parameter-name regexes to ``PartitionSpec``s; ``pjit``/GSPMD inserts
the Megatron collectives from the annotations alone — no hand-written
all-reduces in layer code.

Megatron recipe on (out, in)-layout weights (our FullyConnected keeps the
reference layout, fully_connected.cc):
- column-parallel (shard OUTPUT dim, spec ('tp', None)): QKV projections,
  FFN up-projection, embedding vocab dim;
- row-parallel (shard INPUT dim, spec (None, 'tp')): attention output
  projection, FFN down-projection — its products need one psum, which GSPMD
  emits where the annotations meet.

FSDP is the second mode on the same surface: `FSDPRules` is a shape-driven
rule set that shards every large-enough parameter over the DATA axis —
GSPMD then all-gathers each layer's weights inside the step program and
reduce-scatters its gradients, overlapped with the backward pass.

Resolution order (pinned by tests/test_parallel.py): FIRST MATCH WINS, in
insertion order — there is no most-specific-pattern scoring.  Put narrow
patterns before broad ones; `combined_rules(a, b)` makes every rule of
``a`` outrank every rule of ``b``.
"""

from __future__ import annotations

import os
import re

from .mesh import DP, PP, TP


class ShardingRules:
    """Ordered (regex → PartitionSpec tuple) rules; first match wins.

    ``spec_for(name, shape=None)`` resolves a parameter name to a
    `PartitionSpec`.  The base class ignores ``shape`` (shape-aware
    subclasses like `FSDPRules` consume it); a ``shape=None`` call is
    always legal and resolves regex rules only.  When nothing matches,
    the ``default`` spec applies — ``()`` (fully replicated) unless the
    rule set was built with another default.

    ``composable`` rule sets (class attribute, see `PPRules`) are
    overlays: inside `combined_rules` their matches merge per-dim into
    the winning base spec instead of competing whole-spec.
    """

    composable = False

    def __init__(self, rules=(), default=()):
        self._rules = [(re.compile(p), spec) for p, spec in rules]
        self._default = tuple(default)

    def _match(self, name, shape=None):
        """The first matching spec, or None (→ caller's default)."""
        from jax.sharding import PartitionSpec

        for pat, spec in self._rules:
            if pat.search(name):
                return PartitionSpec(*spec)
        return None

    def spec_for(self, name, shape=None):
        from jax.sharding import PartitionSpec

        spec = self._match(name, shape)
        return spec if spec is not None \
            else PartitionSpec(*self._default)

    def add(self, pattern, spec):
        self._rules.append((re.compile(pattern), tuple(spec)))
        return self


def fsdp_min_size():
    """MXTPU_FSDP_MIN_SIZE: parameters with fewer elements stay
    replicated under FSDP (biases, layernorm scales — sharding them
    buys nothing and costs a collective each)."""
    try:
        return int(os.environ.get("MXTPU_FSDP_MIN_SIZE", "1024"))
    except ValueError:
        return 1024


class FSDPRules(ShardingRules):
    """Shape-driven FSDP: shard each parameter over the data axis.

    Explicit regex ``rules`` outrank the shape heuristic (so TP rules
    can sit in front via ``combined_rules(TRANSFORMER_TP_RULES,
    fsdp_rules(mesh))`` for tp-within-fsdp layouts).  The heuristic
    shards the FIRST dimension the axis size divides; parameters with
    fewer than ``min_size`` elements (default `fsdp_min_size()`), with
    no divisible dimension, or with unknown shape stay replicated.
    """

    def __init__(self, axis=DP, axis_size=None, min_size=None,
                 rules=(), default=()):
        super().__init__(rules=rules, default=default)
        self.axis = axis
        self.axis_size = axis_size
        self.min_size = fsdp_min_size() if min_size is None \
            else int(min_size)

    def _match(self, name, shape=None):
        spec = super()._match(name, shape)
        if spec is not None:
            return spec
        return self._heuristic(shape)

    def _heuristic(self, shape, avoid_dims=()):
        """The shape heuristic alone (no regex): shard the FIRST
        divisible dim not in ``avoid_dims`` — the avoidance hook lets
        `combined_rules` re-run the heuristic around dims a composable
        overlay (e.g. `PPRules`) already claimed, so pp+fsdp composes
        instead of colliding on the stack dim."""
        from jax.sharding import PartitionSpec

        if not shape:
            return None
        n = 1
        for d in shape:
            n *= int(d)
        if n < self.min_size:
            return None
        for dim, d in enumerate(shape):
            if dim in avoid_dims:
                continue
            if self.axis_size is None or \
                    (self.axis_size > 0 and d % self.axis_size == 0):
                entries = [None] * len(shape)
                entries[dim] = self.axis
                return PartitionSpec(*entries)
        return None

    def _match_detail(self, name, shape=None):
        """(spec, from_heuristic) — `combined_rules` uses the flag to
        decide whether a same-dim overlay claim is a hard conflict (an
        explicit regex said so) or a re-route (heuristic moves over)."""
        spec = super()._match(name, shape)
        if spec is not None:
            return spec, False
        return self._heuristic(shape), True


def fsdp_rules(mesh=None, axis=DP, axis_size=None, min_size=None,
               rules=()):
    """`FSDPRules` bound to ``mesh``'s data-axis size (divisibility is
    checked against it); with no mesh, pass ``axis_size`` directly or
    leave both None to shard dim 0 unconditionally."""
    if axis_size is None and mesh is not None:
        axis_size = mesh.shape.get(axis, 1)
    return FSDPRules(axis=axis, axis_size=axis_size, min_size=min_size,
                     rules=rules)


class PPRules(ShardingRules):
    """Pipeline-stage partitioning of the scanned trunk: a COMPOSABLE
    overlay claiming the leading (layer-stack) dimension of every
    ``*_stack_*`` parameter for the ``pp`` axis.

    `combined_rules(PPRules(...), TRANSFORMER_TP_RULES)` merges the
    claim per-dim into the base spec — ``qkv_stack_weight`` resolves to
    ``('pp', 'tp', None)`` — rather than competing whole-spec; two sets
    assigning DIFFERENT axes to the same dim of the same param is a
    hard ValueError.  ``axis_size`` (bound via `pp_rules(mesh)`) guards
    divisibility: a stack whose layer count the stage count does not
    divide stays unclaimed rather than forcing GSPMD padding.
    """

    composable = True

    def __init__(self, axis=PP, axis_size=None, pattern=r"_stack_",
                 rules=None):
        if rules is None:
            rules = [(pattern, (axis,))]
        super().__init__(rules=rules)
        self.axis = axis
        self.axis_size = axis_size

    def _match(self, name, shape=None):
        spec = super()._match(name, shape)
        if spec is None:
            return None
        if self.axis_size and self.axis_size > 1 and shape:
            for dim, e in enumerate(tuple(spec)):
                if e is not None and (dim >= len(shape)
                                      or shape[dim] % self.axis_size):
                    return None
        return spec


def pp_rules(mesh=None, axis=PP, axis_size=None, pattern=r"_stack_"):
    """`PPRules` bound to ``mesh``'s pp-axis size (stack-length
    divisibility is checked against it); with no mesh, pass
    ``axis_size`` directly or leave both None to claim unconditionally."""
    if axis_size is None and mesh is not None:
        axis_size = mesh.shape.get(axis, 1)
    return PPRules(axis=axis, axis_size=axis_size, pattern=pattern)


class EmbeddingRules(ShardingRules):
    """Row-shard `embedding.ShardedEmbedding` tables: a COMPOSABLE
    overlay claiming dim 0 (the vocab dim) of every ``*_embed_table``
    parameter for ``axis`` (default the data axis).

    Row sharding is the memory play for recommender-scale tables — the
    vocab dim is the one that reaches hundreds of millions — and the
    data axis is where the memory is: dp ranks otherwise hold identical
    replicas.  The claim merges per-dim with TP/PP sets (PR 17), so an
    explicit column rule on the output dim coexists: ('dp', 'tp').
    Tables are named ``embed_table`` precisely so the
    ``embedding\\d*_weight`` column-parallel rule in
    `TRANSFORMER_TP_RULES` does not capture them whole-spec first.

    No divisibility guard at the RULE level — the claim always lands,
    so the spec stays stable while a deferred-init table's vocab is
    still unknown.  Divisibility is `param_sharding`'s problem: a
    committed placement cannot be uneven (jax.device_put rejects it),
    so a vocab the axis does not divide degrades that dim to None
    (replicated) at placement time, per mesh — the same table row-
    shards on one layout and replicates on another, and the elastic
    checkpoint plane carries it bitwise between the two.
    """

    composable = True

    def __init__(self, axis=DP, pattern=r"_embed_table$"):
        super().__init__(rules=[(pattern, (axis,))])
        self.axis = axis


def embedding_rules(axis=DP, pattern=r"_embed_table$"):
    """`EmbeddingRules` — named constructor for symmetry with
    `fsdp_rules` / `pp_rules` (no mesh binding needed: there is no
    divisibility guard to size)."""
    return EmbeddingRules(axis=axis, pattern=pattern)


# default rule set for the transformer family (gluon/model_zoo/bert.py
# parameter names)
TRANSFORMER_TP_RULES = ShardingRules(rules=[
    (r"(query|key|value|qkv)_weight$", (TP, None)),   # column-parallel
    (r"(query|key|value|qkv)_bias$", (TP,)),
    (r"proj_weight$", (None, TP)),                    # row-parallel
    (r"ffn1_weight$", (TP, None)),
    (r"ffn1_bias$", (TP,)),
    (r"ffn2_weight$", (None, TP)),
    (r"word_embed_weight$|embedding\d*_weight$", (TP, None)),
    # scanned trunk (ScanTransformerEncoder): (L, ...) stacks — layer
    # dim unsharded, same Megatron column/row split on dims 1+
    (r"qkv_stack_weight$", (None, TP, None)),
    (r"qkv_stack_bias$", (None, TP)),
    (r"proj_stack_weight$", (None, None, TP)),
    (r"ffn1_stack_weight$", (None, TP, None)),
    (r"ffn1_stack_bias$", (None, TP)),
    (r"ffn2_stack_weight$", (None, None, TP)),
], default=())

# serving KV cache: stage-major (L, B, H, Dh, W) along the scanned
# trunk — heads shard on the tp axis exactly like the qkv stacks above,
# so cached keys/values stay resident with the heads that produced them
SERVING_CACHE_AXES = (None, None, TP, None, None)


def serving_cache_sharding(mesh, tp_axis=TP):
    """NamedSharding for a (L, B, H, Dh, W) serving KV cache on ``mesh``
    (None mesh → None, the single-device path)."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec

    spec = tuple(tp_axis if a == TP else a for a in SERVING_CACHE_AXES)
    return NamedSharding(mesh, PartitionSpec(*spec))


# expert parallelism: MoE expert weights shard on their leading E axis
# (gluon/contrib/moe.py MoEFFN); the router gate stays replicated so
# every ep slice routes identically
from .mesh import EP  # noqa: E402

MOE_EP_RULES = ShardingRules(rules=[
    (r"expert_ffn\d_weight$", (EP, None, None)),
    (r"expert_ffn\d_bias$", (EP, None)),
], default=())


class _CombinedRules(ShardingRules):
    """First match wins ACROSS rule sets, shape heuristics included.

    Composable sets (`PPRules`) are the one exception: their matches
    are per-dim CLAIMS merged into the winning base spec.  A claim on a
    dim the base left None (or an absent trailing dim) fills it in; the
    same axis on the same dim is idempotent; a DIFFERENT axis on a dim
    an explicit base rule already assigned raises — silent override
    here would reshard a param two sets disagree about.  When the base
    came from the FSDP shape heuristic, the heuristic re-routes around
    claimed dims instead (it never outranks an explicit claim)."""

    def __init__(self, sets):
        super().__init__()
        self._sets = list(sets)

    def _match(self, name, shape=None):
        base = None            # (tuple spec, from_heuristic, rule set)
        claims = []            # composable (tuple spec, rule set) in order
        for rs in self._sets:
            if getattr(rs, "composable", False):
                spec = rs._match(name, shape)
                if spec is not None:
                    claims.append((tuple(spec), rs))
            elif base is None:
                if hasattr(rs, "_match_detail"):
                    spec, heur = rs._match_detail(name, shape)
                else:
                    spec, heur = rs._match(name, shape), False
                if spec is not None:
                    base = (tuple(spec), heur, rs)
        if not claims:
            if base is None:
                return None
            from jax.sharding import PartitionSpec

            return PartitionSpec(*base[0])
        return self._merge(name, shape, base, claims)

    @staticmethod
    def _merge(name, shape, base, claims):
        from jax.sharding import PartitionSpec

        ndim = len(shape) if shape else max(
            [len(s) for s, _ in claims]
            + ([len(base[0])] if base else []))
        merged = [None] * ndim
        base_spec, base_heur, base_set = base if base else ((), False,
                                                            None)
        for dim, e in enumerate(base_spec[:ndim]):
            merged[dim] = e
        claimed_dims = set()
        for spec, rs in claims:
            for dim, e in enumerate(spec[:ndim]):
                if e is None:
                    continue
                have = merged[dim]
                if have is not None and have != e:
                    if base_heur and dim not in claimed_dims:
                        merged[dim] = None  # heuristic re-routes below
                    else:
                        raise ValueError(
                            "combined_rules: conflicting axes for "
                            f"{name!r} dim {dim}: {e!r} "
                            f"(from {type(rs).__name__}) vs {have!r} — "
                            "two rule sets may not assign different "
                            "axes to the same dim of the same param")
                if e in merged and merged.index(e) != dim:
                    prev = merged.index(e)
                    if base_heur and prev not in claimed_dims:
                        # the duplicate placement came from the FSDP
                        # shape heuristic (e.g. it picked an embedding
                        # table's divisible dim 1 when the vocab dim is
                        # uneven): an explicit claim outranks it — drop
                        # it and let the end-of-merge re-route look for
                        # another dim
                        merged[prev] = None
                    else:
                        raise ValueError(
                            "combined_rules: axis {!r} claimed twice "
                            "for {!r} (dims {} and {}) — a mesh axis "
                            "shards at most one dim per param".format(
                                e, name, prev, dim))
                merged[dim] = e
                claimed_dims.add(dim)
        if base_heur and base_set is not None:
            # the heuristic's dim was taken: re-run it around the
            # claimed dims and fold in what it finds
            redo = base_set._heuristic(shape, avoid_dims=claimed_dims)
            if redo is not None:
                for dim, e in enumerate(tuple(redo)[:ndim]):
                    if e is not None and merged[dim] is None \
                            and e not in merged:
                        merged[dim] = e
        return PartitionSpec(*merged)

    def add(self, pattern, spec):
        # appended rules have the LOWEST precedence, matching the
        # concatenation semantics
        self._sets.append(ShardingRules(rules=[(pattern, spec)]))
        return self


def combined_rules(*rule_sets):
    """Merge rule sets — e.g. combined_rules(TRANSFORMER_TP_RULES,
    MOE_EP_RULES) for a tp×ep transformer, or
    combined_rules(TRANSFORMER_TP_RULES, fsdp_rules(mesh)) for TP
    weights with an FSDP fallback.

    Precedence (pinned by tests/test_parallel.py): FIRST MATCH WINS
    across the concatenation — every rule (and shape heuristic) of an
    earlier set overrides every rule of a later set on conflicting
    names, whole-spec, with no per-dim merging between ordinary sets.
    `PPRules`-style ``composable`` overlays are the exception: their
    per-dim claims merge into the winning base spec, and a conflicting
    axis on the same dim of the same param is a hard ValueError (see
    `_CombinedRules`)."""
    return _CombinedRules(rule_sets)


def match_partition_rules(rules, params):
    """Bulk resolution: ``{name: PartitionSpec}`` for every entry of
    ``params`` (a dict of name → Parameter / array / shape tuple) —
    the pytree-of-specs step between a rule set and `NamedSharding`
    placement."""
    specs = {}
    for name, p in params.items():
        shape = p if isinstance(p, (tuple, list)) \
            else getattr(p, "shape", None)
        specs[name] = rules.spec_for(name, shape)
    return specs


def annotate_block(block, rules):
    """Stamp partition_spec onto every Parameter of a block (consumed by
    ShardedTrainer when laying params over the mesh)."""
    for name, param in block.collect_params().items():
        param.partition_spec = rules.spec_for(name, param.shape)
    return block


def param_sharding(param, mesh):
    """NamedSharding for a Parameter (replicated when no spec/axis).

    Two leniencies so one rule set runs on every mesh: axes the mesh
    doesn't have drop to None, and a sharded dim whose size the axis
    does not divide drops to None too — `jax.device_put` rejects
    uneven committed placements, and an uneven-vocab embedding table
    must replicate rather than fail (`EmbeddingRules`)."""
    from jax.sharding import NamedSharding, PartitionSpec

    spec = param.partition_spec
    if spec is None:
        spec = PartitionSpec()
    # drop axes the mesh doesn't have (lets the same rules run on a
    # dp-only mesh)
    cleaned = []
    shape = getattr(param, "shape", None)
    for dim, entry in enumerate(tuple(spec)):
        if entry is None or entry in mesh.shape:
            if entry is not None and shape is not None \
                    and dim < len(shape) \
                    and shape[dim] % mesh.shape[entry] != 0:
                entry = None
            cleaned.append(entry)
        else:
            cleaned.append(None)
    return NamedSharding(mesh, PartitionSpec(*cleaned))


# -- imperative-path placement (gluon Trainer + CapturedStep) ------------------

def shard_model(block, mesh, mode="tp", rules=None, axis=DP,
                min_size=None, trainer=None):
    """Annotate AND place a gluon block's parameters over ``mesh`` —
    the imperative twin of ShardedTrainer's staging, consumed by
    `gluon.Trainer.train_step`'s captured program (gluon/captured.py).

    Modes on one rule surface:

    - ``mode='tp'``: Megatron tensor parallelism from ``rules``
      (default `TRANSFORMER_TP_RULES`) — Dense/attention weights split
      over the ``tp`` axis; pair with
      `HybridBlock.shard_activations` / `annotate_activations` for the
      activation constraints.
    - ``mode='fsdp'``: every large-enough parameter sharded over the
      data axis (`fsdp_rules`); GSPMD gathers each layer's weights
      inside the step program and reduce-scatters its gradients.
      ``rules`` (if given) overrides the shape heuristic per name.
    - ``mode='pp'``: pipeline stages only — `pp_rules(mesh)` claims the
      leading layer-stack dim of every ``*_stack_*`` param for the
      ``pp`` axis (scanned trunks: ScanTransformerEncoder / scan GPT).
    - ``mode='tp_pp'``: the pp overlay merged over TP (``rules`` or
      `TRANSFORMER_TP_RULES`) — qkv stacks land ('pp','tp',None); with
      a dp axis on the same mesh this is the full tp×pp×dp layout.
    - ``mode='pp_fsdp'``: the pp overlay over the FSDP shape heuristic;
      the heuristic re-routes around the claimed stack dim.

    Initialized parameters (and their gradient buffers) are
    `jax.device_put` onto their `NamedSharding` immediately, making
    them committed sharded arrays every later jit (CachedOp forward,
    captured step, eager grouped update) infers its layout from.
    Aux parameters (``grad_req='null'`` — BatchNorm stats) replicate.
    Also sets the process default mesh.  Returns ``{name: spec}``.

    When RE-sharding a model that already trained (an elastic gang
    reshape, or turning sharding on mid-run), pass the gluon
    ``trainer``: its existing optimizer states are committed to the
    OLD placement and must move with their weights, or the next step's
    jit sees incompatible device sets.  Fresh states (created on the
    first post-shard step) place themselves.
    """
    import jax
    from jax.sharding import PartitionSpec

    from .mesh import set_default_mesh

    # every mode carries the EmbeddingRules overlay as a SIBLING set —
    # composable claims must see the base's heuristic flag, so nesting
    # an already-combined set would lose the FSDP re-route
    emb = EmbeddingRules(axis=axis)
    user = [] if rules is None else [rules]
    if mode == "fsdp":
        sets = [emb] + user \
            + [fsdp_rules(mesh=mesh, axis=axis, min_size=min_size)]
    elif mode == "tp":
        sets = [emb, TRANSFORMER_TP_RULES] if rules is None \
            else [emb] + user
    elif mode == "pp":
        sets = [pp_rules(mesh=mesh), emb] + user
    elif mode == "tp_pp":
        sets = [pp_rules(mesh=mesh), emb,
                TRANSFORMER_TP_RULES if rules is None else rules]
    elif mode == "pp_fsdp":
        sets = [pp_rules(mesh=mesh), emb] + user \
            + [fsdp_rules(mesh=mesh, axis=axis, min_size=min_size)]
    else:
        raise ValueError(f"shard_model: unknown mode {mode!r} (expected "
                         "'tp', 'fsdp', 'pp', 'tp_pp' or 'pp_fsdp')")
    rules = combined_rules(*sets)
    from ..gluon.parameter import DeferredInitializationError

    specs = {}
    for name, p in block.collect_params().items():
        if p.grad_req == "null":
            # aux state (BN running stats) replicates in both modes
            p.partition_spec = PartitionSpec()
        else:
            p.partition_spec = rules.spec_for(name, p.shape)
        specs[name] = p.partition_spec
        sh = param_sharding(p, mesh)
        try:
            nd = p.data()
        except DeferredInitializationError:
            continue  # spec stamps now, placement at materialization
        nd._set_data(jax.device_put(nd._data, sh))
        g = getattr(p, "_grad", None)
        if g is not None and getattr(g, "_data", None) is not None \
                and getattr(p, "_grad_stype", None) != "row_sparse":
            g._set_data(jax.device_put(g._data, sh))
    if trainer is not None:
        from ..optimizer.grouped import _place_state_like

        params = list(trainer._params)
        for upd in getattr(trainer, "_updaters", []):
            for i, st in upd.states.items():
                if st is not None and 0 <= i < len(params):
                    _place_state_like(st, params[i].data())
    set_default_mesh(mesh)
    return specs


def mesh_of_params(params):
    """The Mesh an (iterable of) gluon Parameters is laid over, or None:
    the first committed multi-device `NamedSharding` found wins.  Cheap
    attribute walking only — safe on the per-step path."""
    from jax.sharding import NamedSharding

    for p in params:
        raw = getattr(getattr(p, "_data", None), "_data", None)
        sh = getattr(raw, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh.size > 1:
            return sh.mesh
    return None


def batch_sharding(mesh, dim_size=None, leading=0, axis=DP):
    """NamedSharding splitting the batch dimension (dim ``leading``)
    over the data axis — replicated when the mesh has no dp axis or
    ``dim_size`` is not divisible by it (uneven batches stay whole
    rather than tripping a GSPMD padding path the eager oracle would
    not take)."""
    from jax.sharding import NamedSharding, PartitionSpec

    size = mesh.shape.get(axis, 1)
    if size <= 1 or (dim_size is not None and dim_size % size != 0):
        return NamedSharding(mesh, PartitionSpec())
    return NamedSharding(mesh,
                         PartitionSpec(*([None] * leading + [axis])))


def constrain(x, mesh, spec):
    """`with_sharding_constraint` with the same leniency as
    `param_sharding`: axes absent from the mesh drop to None, and a
    spec longer than ``x``'s rank is a no-op (identity) instead of an
    error — so one activation annotation runs sharded and unsharded."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    if mesh is None or getattr(mesh, "size", 1) <= 1:
        return x
    entries = [e if e is None
               or (e in mesh.shape and mesh.shape[e] > 1) else None
               for e in tuple(spec)]
    ndim = getattr(x, "ndim", None)
    if ndim is None or len(entries) > ndim:
        return x
    # divisibility guard per sharded dim: constraint on a non-divisible
    # dim forces GSPMD padding the eager oracle never sees
    for dim, e in enumerate(entries):
        if e is not None and x.shape[dim] % mesh.shape[e] != 0:
            entries[dim] = None
    sh = NamedSharding(mesh, PartitionSpec(*entries))
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, sh)
    return jax.device_put(x, sh)


def annotate_activations(block, rules, mesh=None):
    """Walk the block tree; any HybridBlock whose NAME matches a rule
    pattern gets `shard_activations(spec, mesh)` — the rules-driven way
    to place Megatron activation constraints without touching model
    code (block names, not parameter names, are matched here)."""
    def walk(b):
        if hasattr(b, "shard_activations"):
            for pat, spec in getattr(rules, "_rules", []):
                if pat.search(getattr(b, "name", "") or ""):
                    b.shard_activations(spec, mesh)
                    break
        for child in getattr(b, "_children", {}).values():
            walk(child)

    walk(block)
    return block
