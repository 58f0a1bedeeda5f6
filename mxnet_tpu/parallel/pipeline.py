"""Pipeline parallelism — forward AND training.

Reference parity: the reference's only model-parallel mechanism is
``group2ctx`` device placement (SURVEY.md §2.5 — nnvm PlaceDevice pass +
example/model-parallel-lstm).  This module is the real thing, TPU-first:
GPipe-style microbatch pipelining as ONE jitted program over the mesh
``pp`` axis using shard_map + ppermute — stage transfers are point-to-point
neighbor pushes on the ICI/DCN torus.

Design: every device holds ITS stage's parameters (stacked stage-major
arrays sharded on pp); the schedule runs num_micro + num_stages - 1 ticks;
at each tick every device runs its stage on the activation it holds, then
ppermutes activations forward one stage.  This is the standard SPMD
"collective pipeline" formulation — no per-stage programs, one XLA module.

The schedule is written as a ``lax.scan``, so reverse-mode AD *derives*
the backward pipeline (activations ride the scan's saved residuals, the
ppermute transposes to the reverse neighbor push) — the GPipe backward
schedule falls out of the forward program instead of being hand-built.
``PipelineTrainer`` stacks a homogeneous Gluon stage list on the pp axis
and compiles forward + backward + optimizer into one XLA program.
"""

from __future__ import annotations

from ..base import MXNetError
from .mesh import PP, default_mesh


def _pipeline_outs(stage_fn, n_stages, n_micro, axis, params, xs,
                   aux=None):
    """shard_map-local differentiable schedule.  params leaves: (1, ...)
    = this device's stage slice; xs: (n_micro, mb, ...) replicated.
    Returns (n_micro, mb, ...) last-stage outputs (replicated); with
    ``aux`` (this device's stage aux slice, e.g. BN running stats —
    stage_fn then has signature (params, aux, x) -> (y, new_aux))
    returns (outs, final_aux).  Aux updates are gated to the ticks where
    the stage holds REAL data — during fill/drain the stage executes on
    garbage and its stats update is discarded."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from .collectives import pvary

    my_params = jax.tree_util.tree_map(lambda p: p[0], params)
    stage = lax.axis_index(axis)
    n_ticks = n_micro + n_stages - 1
    fwd_perm = [(r, (r + 1) % n_stages) for r in range(n_stages)]
    carry0 = pvary(jnp.zeros(xs.shape[1:], xs.dtype), (axis,))
    xs = pvary(xs, (axis,))

    if aux is None:
        def tick(carry, t):
            feed_idx = jnp.clip(t, 0, n_micro - 1)
            my_in = jnp.where(stage == 0, xs[feed_idx], carry)
            y = stage_fn(my_params, my_in)
            return lax.ppermute(y, axis, fwd_perm), y

        _, ys = lax.scan(tick, carry0, jnp.arange(n_ticks))
    else:
        my_aux = jax.tree_util.tree_map(lambda a: a[0], aux)

        def tick(carry, t):
            act, aux_cur = carry
            feed_idx = jnp.clip(t, 0, n_micro - 1)
            my_in = jnp.where(stage == 0, xs[feed_idx], act)
            y, aux_new = stage_fn(my_params, aux_cur, my_in)
            # stage s holds microbatch data only for s <= t < s + n_micro
            valid = (t >= stage) & (t < stage + n_micro)
            aux_cur = jax.tree_util.tree_map(
                lambda n, o: jnp.where(valid, n, o), aux_new, aux_cur)
            return (lax.ppermute(y, axis, fwd_perm), aux_cur), y

        (_, final_aux), ys = lax.scan(tick, (carry0, my_aux),
                                      jnp.arange(n_ticks))
    # microbatch m leaves the last stage at tick m + n_stages - 1
    outs = ys[n_stages - 1:]
    # only the last stage holds real outputs; broadcast to all
    outs = lax.psum(
        jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
        axis)
    if aux is None:
        return outs
    final_aux = jax.tree_util.tree_map(lambda a: a[None], final_aux)
    return outs, final_aux


def _schedule_1f1b(n_stages, n_micro):
    """Host-side greedy 1F1B schedule.

    Returns (table_f, table_b, n_ticks, bubble): (n_ticks, n_stages)
    int arrays — table_f[t, s] is the microbatch whose FORWARD stage s
    runs at tick t (−1: none), table_b likewise for backward; bubble is
    the measured idle fraction of device-ticks.  The greedy rule (do a
    ready backward, else a forward while in-flight < n_stages − s) is
    the classic non-interleaved 1F1B: in-flight activations per stage
    are bounded by n_stages (not n_micro, GPipe's bound).
    """
    S, M = n_stages, n_micro
    fwd_ready = [list(range(M))] + [[] for _ in range(S - 1)]
    bwd_ready = [[] for _ in range(S)]
    # (arrival_tick, mb) events scheduled into the future
    fwd_arrivals = [[] for _ in range(S)]
    bwd_arrivals = [[] for _ in range(S)]
    inflight = [0] * S
    done_bwd = [0] * S
    rows_f, rows_b = [], []
    t = 0
    while any(d < M for d in done_bwd):
        for s in range(S):
            fwd_ready[s] += [m for at, m in fwd_arrivals[s] if at <= t]
            fwd_arrivals[s] = [(at, m) for at, m in fwd_arrivals[s]
                               if at > t]
            bwd_ready[s] += [m for at, m in bwd_arrivals[s] if at <= t]
            bwd_arrivals[s] = [(at, m) for at, m in bwd_arrivals[s]
                               if at > t]
        row_f, row_b = [-1] * S, [-1] * S
        for s in range(S):
            if bwd_ready[s]:
                b = min(bwd_ready[s])
                bwd_ready[s].remove(b)
                row_b[s] = b
                inflight[s] -= 1
                done_bwd[s] += 1
                if s > 0:
                    bwd_arrivals[s - 1].append((t + 1, b))
            elif fwd_ready[s] and inflight[s] < S - s:
                f = min(fwd_ready[s])
                fwd_ready[s].remove(f)
                row_f[s] = f
                inflight[s] += 1
                if s < S - 1:
                    fwd_arrivals[s + 1].append((t + 1, f))
                else:
                    bwd_arrivals[s].append((t + 1, f))
            # else: bubble
        rows_f.append(row_f)
        rows_b.append(row_b)
        t += 1
        if t > 4 * (M + S) + 8:  # safety against a schedule bug
            raise MXNetError("1F1B schedule did not converge")
    n_ticks = len(rows_f)
    busy = sum(1 for row in rows_f for v in row if v >= 0) + \
        sum(1 for row in rows_b for v in row if v >= 0)
    bubble = 1.0 - busy / float(S * n_ticks)
    return rows_f, rows_b, n_ticks, bubble


def gpipe_bubble_fraction(n_stages, n_micro):
    """Analytic GPipe bubble: (S−1)/(M+S−1) per fwd/bwd pass."""
    return (n_stages - 1) / float(n_micro + n_stages - 1)


def _pipeline_1f1b_grads(stage_apply, epi_loss, n_stages, n_micro, axis,
                         tables, params, aux, epi_vals, hs, ys):
    """shard_map-local 1F1B schedule with a HAND-ROLLED backward.

    Unlike the GPipe path (AD through the fwd scan, residuals O(ticks)),
    each device keeps an S-slot activation buffer (the 1F1B in-flight
    bound) and recomputes its stage inside ``jax.vjp`` at the backward
    tick — forward and backward interleave in ONE scan, dk/cotangents
    ride reverse ppermutes, per-stage param grads accumulate locally
    (already pp-sharded).

    stage_apply(my_params, my_aux, x, key_idx) -> (y, new_aux)
    epi_loss(epi_vals, y, y_labels_mb, mb_idx) -> scalar per-mb loss
    hs, ys: (n_micro, mb, ...) replicated.
    Returns (loss, trunk_grads (1,...), epi_grads, dH (n_micro, mb, ...),
    final_aux (1,...)).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from .collectives import pvary

    S, M = n_stages, n_micro
    table_f, table_b = tables
    n_ticks = table_f.shape[0]
    my_params = jax.tree_util.tree_map(lambda p: p[0], params)
    my_aux = jax.tree_util.tree_map(lambda a: a[0], aux)
    stage = lax.axis_index(axis)
    fwd_perm = [(r, (r + 1) % S) for r in range(S)]
    bwd_perm = [(r, (r - 1) % S) for r in range(S)]
    mb_shape = hs.shape[1:]
    act_dtype = hs.dtype

    def pv(x):
        return pvary(x, (axis,))

    # mark replicated epilogue params varying BEFORE they enter the
    # per-device cond: differentiating a varying computation wrt an
    # UNVARYING input makes the vjp transpose insert a psum inside the
    # branch — a collective only the last stage would execute
    # (rendezvous deadlock).  Varying-in, varying-cotangent keeps the
    # branch collective-free; the explicit psum below does the merge.
    epi_vals = jax.tree_util.tree_map(pv, list(epi_vals))

    zeros_mb = lambda: pv(jnp.zeros(mb_shape, act_dtype))
    X0 = pv(jnp.zeros((S,) + mb_shape, act_dtype))
    G0 = pv(jnp.zeros((S,) + mb_shape, act_dtype))
    dp0 = jax.tree_util.tree_map(lambda p: pv(jnp.zeros_like(p)),
                                 my_params)
    depi0 = jax.tree_util.tree_map(lambda p: pv(jnp.zeros_like(p)),
                                   list(epi_vals))
    dH0 = pv(jnp.zeros((M,) + mb_shape, act_dtype))
    hs = pv(hs)
    ys = pv(ys)

    def tick(carry, t):
        X, G, fmsg, bmsg, aux_c, dp, depi, dH, loss_acc = carry
        # receive what neighbors ppermuted at the end of tick t-1
        fl = table_f[jnp.maximum(t - 1, 0), jnp.maximum(stage - 1, 0)]
        wr_x = (t >= 1) & (stage >= 1) & (fl >= 0)
        xi = jnp.maximum(fl, 0) % S
        X = X.at[xi].set(jnp.where(wr_x, fmsg, X[xi]))
        br = table_b[jnp.maximum(t - 1, 0),
                     jnp.minimum(stage + 1, S - 1)]
        wr_g = (t >= 1) & (stage < S - 1) & (br >= 0)
        gi = jnp.maximum(br, 0) % S
        G = G.at[gi].set(jnp.where(wr_g, bmsg, G[gi]))

        f = table_f[t, stage]
        b = table_b[t, stage]
        fc = jnp.clip(f, 0, M - 1)
        bc = jnp.clip(b, 0, M - 1)
        x_in = jnp.where(stage == 0, hs[fc], X[fc % S])
        x_res = jnp.where(stage == 0, hs[bc], X[bc % S])

        def do_fwd(_):
            y, aux_new = stage_apply(my_params, aux_c, x_in,
                                     fc * S + stage)
            return y.astype(act_dtype), aux_new

        def skip_fwd(_):
            return zeros_mb(), aux_c

        y_out, aux_c = lax.cond(f >= 0, do_fwd, skip_fwd, None)

        def do_bwd(_):
            def last(_):
                def f2(p, x, ev):
                    y2, _ = stage_apply(p, aux_c, x, bc * S + stage)
                    return epi_loss(ev, y2, ys[bc], bc)

                lval, vjp = jax.vjp(f2, my_params, x_res, epi_vals)
                dp_b, dx_b, depi_b = vjp(
                    pv(jnp.asarray(1.0 / M, lval.dtype)))
                return (jax.tree_util.tree_map(pv, dp_b),
                        pv(dx_b.astype(act_dtype)),
                        jax.tree_util.tree_map(pv, list(depi_b)),
                        pv((lval / M).astype(jnp.float32)))

            def mid(_):
                dy = G[bc % S]

                def f3(p, x):
                    y2, _ = stage_apply(p, aux_c, x, bc * S + stage)
                    return y2.astype(act_dtype)

                _, vjp = jax.vjp(f3, my_params, x_res)
                dp_b, dx_b = vjp(dy)
                return jax.tree_util.tree_map(pv, dp_b), \
                    pv(dx_b.astype(act_dtype)), \
                    jax.tree_util.tree_map(
                        lambda z: pv(jnp.zeros_like(z)),
                        list(epi_vals)), \
                    pv(jnp.asarray(0.0, jnp.float32))

            return lax.cond(stage == S - 1, last, mid, None)

        def skip_bwd(_):
            zt = lambda tree: jax.tree_util.tree_map(
                lambda z: pv(jnp.zeros_like(z)), tree)
            return (zt(my_params), zeros_mb(), zt(list(epi_vals)),
                    pv(jnp.asarray(0.0, jnp.float32)))

        dp_b, dx_b, depi_b, lval = lax.cond(b >= 0, do_bwd, skip_bwd,
                                            None)
        dp = jax.tree_util.tree_map(jnp.add, dp, dp_b)
        depi = jax.tree_util.tree_map(jnp.add, depi, depi_b)
        loss_acc = loss_acc + lval
        take = ((stage == 0) & (b >= 0)).astype(dH.dtype)
        dH = dH.at[bc].add(take * dx_b)
        bmsg_new = jnp.where(stage > 0, dx_b, jnp.zeros_like(dx_b))
        fmsg_new = lax.ppermute(y_out, axis, fwd_perm)
        bmsg_new = lax.ppermute(bmsg_new, axis, bwd_perm)
        return (X, G, fmsg_new, bmsg_new, aux_c, dp, depi, dH,
                loss_acc), None

    carry0 = (X0, G0, zeros_mb(), zeros_mb(), my_aux, dp0, depi0, dH0,
              pv(jnp.asarray(0.0, jnp.float32)))
    (X, G, _, _, aux_f, dp, depi, dH, loss_acc), _ = lax.scan(
        tick, carry0, jnp.arange(n_ticks))

    loss = lax.psum(loss_acc, axis)      # only the last stage adds loss
    dH = lax.psum(dH, axis)              # only stage 0 writes dH
    depi = jax.tree_util.tree_map(lambda g: lax.psum(g, axis), depi)
    dp = jax.tree_util.tree_map(lambda g: g[None], dp)
    aux_f = jax.tree_util.tree_map(lambda a: a[None], aux_f)
    return loss, dp, depi, dH, aux_f


def pipeline_apply(stage_fn, params_stacked, x_micro, mesh=None, axis=PP):
    """Run a pipelined forward (differentiable).

    stage_fn(stage_params, x) -> y : the per-stage computation (all stages
    must share one signature/shape — the usual homogeneous-transformer
    assumption).
    params_stacked: pytree whose leaves have leading dim = n_stages,
    sharded on `axis`.
    x_micro: (n_micro, mb, ...) microbatched input, replicated.
    Returns (n_micro, mb, ...) outputs from the LAST stage (replicated).
    """
    import jax
    from jax.sharding import PartitionSpec

    mesh = mesh or default_mesh()
    if mesh is None:
        raise MXNetError("pipeline_apply needs a mesh")
    n_stages = mesh.shape.get(axis, 1)
    n_micro = x_micro.shape[0]
    if n_micro < n_stages:
        raise MXNetError(
            f"pipeline needs n_micro ({n_micro}) >= n_stages "
            f"({n_stages}) to fill the pipe")

    pspec = jax.tree_util.tree_map(
        lambda _: PartitionSpec(axis), params_stacked)
    xspec = PartitionSpec()

    def local(params, xs):
        return _pipeline_outs(stage_fn, n_stages, n_micro, axis, params,
                              xs)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(pspec, xspec),
                       out_specs=xspec)
    return fn(params_stacked, x_micro)


def stack_stage_params(per_stage_params):
    """[stage0_tree, stage1_tree, ...] → one tree with stacked leaves
    (leading dim = n_stages) ready to shard on pp."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


class PipelineTrainer:
    """GPipe training of a homogeneous stage list as ONE XLA program.

    The model is a list of structurally-identical Gluon blocks (or a
    (Hybrid)Sequential whose children divide evenly into such groups):
    transformer layers, the Dense towers of the reference's
    model-parallel-lstm example, etc.  Per-stage parameters are stacked
    (leading dim = n_stages) and sharded on the mesh ``pp`` axis, so each
    device holds exactly its stage; forward runs the scan schedule above,
    backward is its AD transpose (the reverse pipeline), and the
    optimizer updates each stage's shard in place — all in one jit with
    donated buffers.

    A real model needs more than the homogeneous trunk: ``prologue``
    (e.g. token embedding) runs before the pipe and ``epilogue`` (e.g.
    the MLM head) after it.  Their parameters are replicated on the pp
    axis and their compute is bulk-synchronous around the scan schedule —
    on an SPMD pp mesh every device redundantly computes them, which
    costs no wall-clock (the alternative is those devices idling) and
    keeps the scanned schedule shape-uniform, which is what lets one XLA
    program express the whole pipeline.  This pipelines a full BERT
    (embedding + N encoder layers + MLM head); see
    gluon.model_zoo.bert.bert_pipeline_parts.

    Aux state (BatchNorm running stats) is supported: per-stage aux is
    stacked on pp like the trainable params, threaded through the scan
    carry with updates gated to real-data ticks, and excluded from the
    optimizer — so BN-bearing towers (ResNet!) pipeline.  Remaining v1
    limits (documented, reference has no pipeline at all): trunk stages
    share one input/output shape; the loss attaches to the epilogue's
    (or last stage's) output.
    """

    def __init__(self, stages, loss_fn, optimizer="sgd",
                 optimizer_params=None, mesh=None, n_microbatches=None,
                 axis=PP, prologue=None, epilogue=None,
                 schedule="gpipe"):
        import jax

        from .trainer import _PureOptimizer

        mesh = mesh or default_mesh()
        if mesh is None:
            raise MXNetError("PipelineTrainer needs a mesh")
        if schedule not in ("gpipe", "1f1b"):
            raise MXNetError(
                f"PipelineTrainer: unknown schedule {schedule!r} "
                "('gpipe' or '1f1b')")
        self.mesh = mesh
        self.axis = axis
        self.n_stages = mesh.shape.get(axis, 1)
        self.loss_fn = loss_fn
        self.stages = self._as_stages(stages)
        self.prologue = prologue
        self.epilogue = epilogue
        self.schedule = schedule
        self.n_micro = int(n_microbatches or self.n_stages)
        if self.n_micro < self.n_stages:
            raise MXNetError("n_microbatches must be >= n_stages")
        if schedule == "1f1b":
            self._1f1b_tables = _schedule_1f1b(self.n_stages,
                                               self.n_micro)
            self.bubble_fraction = self._1f1b_tables[3]
            self.schedule_ticks = self._1f1b_tables[2]
        else:
            self.bubble_fraction = gpipe_bubble_fraction(self.n_stages,
                                                         self.n_micro)
            # fwd scan + its AD transpose
            self.schedule_ticks = 2 * (self.n_micro + self.n_stages - 1)
        opt_kwargs = dict(optimizer_params or {})
        lr = opt_kwargs.pop("learning_rate", opt_kwargs.pop("lr", 0.01))
        self.optimizer = _PureOptimizer(optimizer, lr=lr, **opt_kwargs)
        self._num_update = 0
        self._initialized = False
        self._step_fn = None

    def _as_stages(self, stages):
        if isinstance(stages, (list, tuple)):
            stage_list = list(stages)
        else:  # a Sequential-like block
            children = list(stages._children.values())
            if not children or len(children) % self.n_stages:
                raise MXNetError(
                    f"cannot split {len(children)} layers into "
                    f"{self.n_stages} equal pipeline stages")
            per = len(children) // self.n_stages
            if per == 1:
                stage_list = children
            else:
                from ..gluon.nn import HybridSequential

                stage_list = []
                for s in range(self.n_stages):
                    seq = HybridSequential(prefix=f"ppstage{s}_")
                    for c in children[s * per:(s + 1) * per]:
                        seq.add(c)
                    stage_list.append(seq)
        if len(stage_list) != self.n_stages:
            raise MXNetError(
                f"got {len(stage_list)} stages for a {self.n_stages}-way "
                f"pp mesh")
        return stage_list

    # -- staging ---------------------------------------------------------------

    @staticmethod
    def _split_params(block):
        """(trainable items, aux items) in structural order."""
        items = list(block.collect_params().items())
        return ([(n, p) for n, p in items if p.grad_req != "null"],
                [(n, p) for n, p in items if p.grad_req == "null"])

    def _stage_params(self, example):
        """Materialize deferred shapes, stack per-stage params on pp;
        prologue/epilogue params are replicated.  Aux params (BN running
        stats) are stacked/replicated the same way but live outside the
        optimizer — they update through the aux_collector protocol."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        from .. import autograd as _ag
        from ..gluon.block import _TRACE

        # resolve deferred init by running the whole chain once
        prev = _TRACE.force_eager
        _TRACE.force_eager = True
        try:
            with _ag.pause():
                h = example
                if self.prologue is not None:
                    h = self.prologue(h)
                for s in self.stages:
                    h = s(h)
                if self.epilogue is not None:
                    self.epilogue(h)
        finally:
            _TRACE.force_eager = prev

        # structural (registration) order, NOT name sort: lexicographic
        # names permute across stages once indices hit two digits
        # (dense9 > dense10), mis-pairing weights between stages
        split = [self._split_params(s) for s in self.stages]
        per_stage = [[p.data()._data for _, p in tr] for tr, _ in split]
        per_stage_aux = [[p.data()._data for _, p in ax]
                         for _, ax in split]
        shapes = [[tuple(a.shape) for a in vals] for vals in per_stage]
        ashapes = [[tuple(a.shape) for a in vals]
                   for vals in per_stage_aux]
        if any(sh != shapes[0] for sh in shapes[1:]) or \
                any(sh != ashapes[0] for sh in ashapes[1:]):
            raise MXNetError(
                f"pipeline stages are not structurally identical: "
                f"{shapes} / aux {ashapes}")
        # template ids come from stage 0; its forward executes every stage
        self._template = self.stages[0]
        tmpl_tr, tmpl_ax = self._split_params(self._template)
        self._template_ids = [id(p) for _, p in tmpl_tr]
        self._template_aux_ids = [id(p) for _, p in tmpl_ax]
        self._template_aux_names = [p.name for _, p in tmpl_ax]
        stacked = [jnp.stack([vals[j] for vals in per_stage])
                   for j in range(len(per_stage[0]))]
        self._pspec = NamedSharding(self.mesh, PartitionSpec(self.axis))
        self._repl = NamedSharding(self.mesh, PartitionSpec())
        self._n_trunk = len(stacked)
        param_vals = [jax.device_put(a, self._pspec) for a in stacked]
        shardings = [self._pspec] * len(stacked)
        wd = [p.wd_mult for _, p in tmpl_tr]
        lr = [p.lr_mult for _, p in tmpl_tr]
        self._trunk_aux_vals = [
            jax.device_put(jnp.stack([vals[j] for vals in per_stage_aux]),
                           self._pspec)
            for j in range(len(per_stage_aux[0]))]

        # prologue/epilogue: replicated leaves appended after the trunk
        self._edge_ids = {}
        self._edge_aux = {}
        for name, block in (("prologue", self.prologue),
                            ("epilogue", self.epilogue)):
            if block is None:
                self._edge_ids[name] = []
                self._edge_aux[name] = ([], [], [])
                continue
            items, aux_items = self._split_params(block)
            self._edge_ids[name] = [id(p) for _, p in items]
            self._edge_aux[name] = (
                [id(p) for _, p in aux_items],
                [p.name for _, p in aux_items],
                [jax.device_put(p.data()._data, self._repl)
                 for _, p in aux_items])
            param_vals += [jax.device_put(p.data()._data, self._repl)
                           for _, p in items]
            shardings += [self._repl] * len(items)
            wd += [p.wd_mult for _, p in items]
            lr += [p.lr_mult for _, p in items]

        self._param_vals = param_vals
        self._param_shardings = shardings
        self._opt_state = [
            tuple(jax.device_put(s, sh) for s in states)
            for states, sh in zip(self.optimizer.init_state(param_vals),
                                  shardings)]
        self._wd_mults = wd
        self._lr_mults = lr
        self._initialized = True

    def _build_step(self, batch_shape):
        import jax
        import jax.numpy as jnp

        from .. import autograd as _ag
        from .. import random as _random
        from ..gluon.block import _TRACE

        template = self._template
        t_ids = list(self._template_ids)
        loss_block = self.loss_fn
        optimizer = self.optimizer
        n_stages, n_micro, axis = self.n_stages, self.n_micro, self.axis
        mesh = self.mesh
        wd_mults = tuple(self._wd_mults)
        lr_mults = tuple(self._lr_mults)

        from jax.sharding import PartitionSpec

        n_trunk = self._n_trunk
        prologue, epilogue = self.prologue, self.epilogue
        pro_ids = list(self._edge_ids["prologue"])
        epi_ids = list(self._edge_ids["epilogue"])
        n_pro = len(pro_ids)
        a_ids = list(self._template_aux_ids)
        a_names = list(self._template_aux_names)
        n_aux = len(a_ids)
        pro_a_ids, pro_a_names, _ = self._edge_aux["prologue"]
        epi_a_ids, epi_a_names, _ = self._edge_aux["epilogue"]

        def _run_block(block, ids, vals, x, aux_ids=(), aux_names=(),
                       aux_vals=()):
            """Run a gluon block functionally; returns (out, new_aux)
            where new_aux follows aux_names order (unchanged entries
            keep their input value)."""
            from ..gluon.block import param_override_scope

            pm = dict(zip(ids, vals))
            pm.update(zip(aux_ids, aux_vals))
            col = {}
            with param_override_scope(pm, col), _ag.train_mode():
                out = block.forward(x)
            return out, [col.get(n, v)
                         for n, v in zip(aux_names, aux_vals)]

        if n_aux:
            def stage_fn(stage_vals, stage_aux, x):
                return _run_block(template, t_ids, stage_vals, x,
                                  a_ids, a_names, stage_aux)
        else:
            def stage_fn(stage_vals, x):
                out, _ = _run_block(template, t_ids, stage_vals, x)
                return out

        pspec_tree = [PartitionSpec(axis) for _ in range(n_trunk)]
        aspec_tree = [PartitionSpec(axis) for _ in range(n_aux)]

        def fwd_micro(trunk_vals, trunk_aux, xs):
            if n_aux:
                local = lambda params, aux_, xs_: _pipeline_outs(
                    stage_fn, n_stages, n_micro, axis, params, xs_,
                    aux=aux_)
                fn = jax.shard_map(
                    local, mesh=mesh,
                    in_specs=(pspec_tree, aspec_tree, PartitionSpec()),
                    out_specs=(PartitionSpec(), aspec_tree))
                return fn(trunk_vals, trunk_aux, xs)
            local = lambda params, xs_: _pipeline_outs(
                stage_fn, n_stages, n_micro, axis, params, xs_)
            fn = jax.shard_map(local, mesh=mesh,
                               in_specs=(pspec_tree, PartitionSpec()),
                               out_specs=PartitionSpec())
            return fn(trunk_vals, xs), []

        def pure_step(param_vals, opt_state, trunk_aux, pro_aux, epi_aux,
                      x, y, key, lr, t):
            def loss_of(pv):
                trunk = pv[:n_trunk]
                pro = pv[n_trunk:n_trunk + n_pro]
                epi = pv[n_trunk + n_pro:]
                with _random.key_scope(key):
                    h = x
                    pro_aux_new = list(pro_aux)
                    if prologue is not None:
                        # replicated on pp: every device computes the
                        # embedding for the full batch (no wall-clock
                        # cost — they'd be idle), grads come out
                        # identical, optimizer updates stay replicated
                        h, pro_aux_new = _run_block(
                            prologue, pro_ids, pro, h, pro_a_ids,
                            pro_a_names, pro_aux)
                    hs = h.reshape((n_micro, -1) + h.shape[1:])
                    outs, trunk_aux_new = fwd_micro(trunk, trunk_aux, hs)
                    outs = outs.reshape((-1,) + outs.shape[2:])
                    epi_aux_new = list(epi_aux)
                    if epilogue is not None:
                        outs, epi_aux_new = _run_block(
                            epilogue, epi_ids, epi, outs, epi_a_ids,
                            epi_a_names, epi_aux)
                    loss = loss_block(outs, y) \
                        if loss_block is not None else outs
                return jnp.mean(loss), (trunk_aux_new, pro_aux_new,
                                        epi_aux_new)

            (loss, new_aux), grads = jax.value_and_grad(
                loss_of, has_aux=True)(param_vals)
            new_p, new_s = optimizer.apply(
                param_vals, grads, opt_state, lr, t, wd_mults, lr_mults,
                1.0)
            return new_p, new_s, new_aux, loss

        # -- 1F1B: hand-rolled interleaved fwd/bwd schedule -------------------
        if self.schedule == "1f1b":
            if self._edge_aux["epilogue"][0]:
                raise MXNetError(
                    "schedule='1f1b' does not support aux params in the "
                    "epilogue (the per-microbatch loss vjp would need "
                    "per-tick aux merging); use schedule='gpipe'")
            rows_f, rows_b, n_ticks, _ = self._1f1b_tables
            table_f = jnp.asarray(rows_f, jnp.int32)
            table_b = jnp.asarray(rows_b, jnp.int32)

            def pure_step_1f1b(param_vals, opt_state, trunk_aux,
                               pro_aux, epi_aux, x, y, key, lr, t):
                trunk = param_vals[:n_trunk]
                pro = param_vals[n_trunk:n_trunk + n_pro]
                epi = param_vals[n_trunk + n_pro:]

                def stage_apply(p, a, xin, key_idx):
                    # per-(microbatch, stage) key: the backward tick's
                    # recompute must draw the SAME randomness (dropout)
                    # as the forward tick did
                    with _random.key_scope(jax.random.fold_in(key,
                                                              key_idx)):
                        if n_aux:
                            return stage_fn(p, a, xin)
                        return stage_fn(p, xin), []

                def epi_loss(ev, yout, y_lbl, mb_idx):
                    with _random.key_scope(
                            jax.random.fold_in(key, 1000003 + mb_idx)):
                        out = yout
                        if epilogue is not None:
                            out, _ = _run_block(epilogue, epi_ids, ev,
                                                yout)
                        l = loss_block(out, y_lbl) \
                            if loss_block is not None else out
                        return jnp.mean(l)

                pro_aux_new = list(pro_aux)
                if prologue is not None:
                    def pro_fwd(pv_):
                        with _random.key_scope(key):
                            return _run_block(
                                prologue, pro_ids, pv_, x, pro_a_ids,
                                pro_a_names, pro_aux)
                    (h, pro_aux_new), pro_vjp = jax.vjp(pro_fwd, pro,
                                                        has_aux=False)
                else:
                    h, pro_vjp = x, None
                hs = h.reshape((n_micro, -1) + h.shape[1:])
                ys = y.reshape((n_micro, -1) + y.shape[1:])

                def local(params, aux_, epi_, hs_, ys_):
                    return _pipeline_1f1b_grads(
                        stage_apply, epi_loss, n_stages, n_micro, axis,
                        (table_f, table_b), params, aux_, epi_, hs_,
                        ys_)

                fn = jax.shard_map(
                    local, mesh=mesh,
                    in_specs=(pspec_tree, aspec_tree,
                              [PartitionSpec()] * len(epi_ids),
                              PartitionSpec(), PartitionSpec()),
                    out_specs=(PartitionSpec(), pspec_tree,
                               [PartitionSpec()] * len(epi_ids),
                               PartitionSpec(), aspec_tree))
                loss, trunk_g, epi_g, dH, trunk_aux_new = fn(
                    trunk, trunk_aux, list(epi), hs, ys)
                if prologue is not None:
                    dH_full = dH.reshape(h.shape).astype(h.dtype)
                    (pro_g,) = pro_vjp((dH_full, [jnp.zeros_like(a) for
                                                  a in pro_aux_new]))
                else:
                    pro_g = []
                grads = list(trunk_g) + list(pro_g) + list(epi_g)
                new_p, new_s = optimizer.apply(
                    param_vals, grads, opt_state, lr, t, wd_mults,
                    lr_mults, 1.0)
                return new_p, new_s, (trunk_aux_new, pro_aux_new,
                                      list(epi_aux)), loss

            pure_step = pure_step_1f1b

        aux_shardings = ([self._pspec] * n_aux,
                         [self._repl] * len(pro_a_ids),
                         [self._repl] * len(epi_a_ids))
        with self.mesh:
            self._step_fn = jax.jit(
                pure_step,
                in_shardings=(
                    list(self._param_shardings),
                    [tuple(sh for _ in st)
                     for st, sh in zip(self._opt_state,
                                       self._param_shardings)],
                    *aux_shardings,
                    self._repl, self._repl, None, None, None),
                out_shardings=(
                    list(self._param_shardings),
                    [tuple(sh for _ in st)
                     for st, sh in zip(self._opt_state,
                                       self._param_shardings)],
                    aux_shardings,
                    self._repl),
                donate_argnums=(0, 1, 2, 3, 4))

    # -- public API ------------------------------------------------------------

    def step(self, data, label):
        """One pipelined training step; batch dim 0 must divide into
        n_microbatches."""
        import jax
        import jax.numpy as jnp

        from .. import random as _random
        from ..ndarray.ndarray import NDArray, _from_jax

        x = data._data if isinstance(data, NDArray) else jnp.asarray(data)
        y = label._data if isinstance(label, NDArray) \
            else jnp.asarray(label)
        if x.shape[0] % self.n_micro:
            raise MXNetError(
                f"batch {x.shape[0]} not divisible by n_microbatches "
                f"{self.n_micro}")
        if not self._initialized:
            mb = x.shape[0] // self.n_micro
            self._stage_params(_from_jax(x[:mb]))
            self._build_step(x.shape)
        x = jax.device_put(x, self._repl)
        y = jax.device_put(y, self._repl)
        self._num_update += 1
        t = self._num_update
        lr = self.optimizer.lr_at(t)
        key = _random.next_key()
        aux = (self._trunk_aux_vals, self._edge_aux["prologue"][2],
               self._edge_aux["epilogue"][2])
        (self._param_vals, self._opt_state, new_aux, loss) = \
            self._step_fn(
                self._param_vals, self._opt_state, *aux, x, y, key,
                jnp.asarray(lr, jnp.float32), jnp.asarray(t, jnp.float32))
        self._trunk_aux_vals = new_aux[0]
        self._edge_aux["prologue"] = self._edge_aux["prologue"][:2] + \
            (new_aux[1],)
        self._edge_aux["epilogue"] = self._edge_aux["epilogue"][:2] + \
            (new_aux[2],)
        return _from_jax(loss)

    def sync_params(self):
        """Write stage slices (and replicated prologue/epilogue values)
        back into the Gluon Parameters — trainable AND aux."""
        for s, stage in enumerate(self.stages):
            tr, ax = self._split_params(stage)
            for j, (_, p) in enumerate(tr):
                p.data()._set_data(self._param_vals[j][s])
            for j, (_, p) in enumerate(ax):
                p.data()._set_data(self._trunk_aux_vals[j][s])
        i = self._n_trunk
        for name, block in (("prologue", self.prologue),
                            ("epilogue", self.epilogue)):
            if block is None:
                continue
            tr, ax = self._split_params(block)
            for _, p in tr:
                p.data()._set_data(self._param_vals[i])
                i += 1
            for (_, p), v in zip(ax, self._edge_aux[name][2]):
                p.data()._set_data(v)
