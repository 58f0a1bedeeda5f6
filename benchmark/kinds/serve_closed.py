"""Traffic kind ``serve_closed``: a closed loop of clients against
``ServingEngine`` + ``ContinuousBatcher``.

The traffic file gives ``clients``, ``batch_buckets``, optionally
``batcher`` (keyword arguments of ``ContinuousBatcher``) and two length
tables of ``clients`` entries each.  In round r the clients between them
hold every entry of each table exactly once, by two permutations drawn
from the seed: the seed moves who asks what and in what company, never
how much is asked.  Sampling is greedy.

One thread drives all clients through the futures the batcher returns.
A client submits again the moment its own answer arrives; the window
admits a round whole or not at all (`window.py`: ``group``).  No prompt
is drawn between a round's first and last submission: set-up makes round
0's, and the driver makes each client's next one while the batcher is at
work (no answer for ``QUIET_S``).  A prompt that is missing all the same
is drawn where it is needed and counted (``prompts_drawn_late``).
"""

import concurrent.futures

import numpy as np


# no answer for this long: the round is handed over and the batcher is
# serving it, so the driver's thread has nothing to hold up.  Well over
# the gap between two answers of one group (the batcher hands them over
# one by one, under a millisecond apart), well under any cell's prefill
QUIET_S = 0.02


# -- traffic -------------------------------------------------------------------

def schedule(traffic, seed, rounds):
    """``rounds`` rounds of (prompt length, output length) per client:
    array (rounds, clients, 2)."""
    n = traffic["clients"]
    prompts = np.asarray(traffic["prompt_lengths"])
    outputs = np.asarray(traffic["output_lengths"])
    if len(prompts) != n or len(outputs) != n:
        raise ValueError("serve_closed: each length table needs one entry "
                         "per client")
    rng = np.random.default_rng([int(seed), 1])
    out = np.empty((rounds, n, 2), np.int64)
    for r in range(rounds):
        out[r, :, 0] = prompts[rng.permutation(n)]
        out[r, :, 1] = outputs[rng.permutation(n)]
    return out


def prompt_ids(seed, client, r, length, vocab):
    rng = np.random.default_rng([int(seed), 2, client, r])
    return rng.integers(0, vocab, length).astype(np.int32)


def planned_prompt(seed, plan, vocab, client, r):
    """The prompt ``plan`` has ``client`` send in round ``r``."""
    return prompt_ids(seed, client, r, int(plan[r, client, 0]), vocab)


# -- set-up --------------------------------------------------------------------

def setup(ctx):
    import jax.numpy as jnp

    from mxnet_tpu import serving

    from benchmark import program

    cell, traffic = ctx["cell"], ctx["cell"]["traffic"]
    net, leaves = program.build_net(cell, ctx["seed"], ctx["platform"])
    ctx["lap"]("model built, seeded weights placed")
    engine = serving.ServingEngine(
        net, batch_buckets=tuple(traffic["batch_buckets"]),
        dtype=jnp.dtype(cell["config"]["program"]["dtype"]))
    vocab = cell["config"]["vocab_size"]
    # warm exactly the programs this traffic dispatches: one group with
    # the table's prompt lengths compiles its (batch, prefill) bucket and
    # the decode program through the engine's own request path
    n = traffic["clients"]
    warm = [prompt_ids(ctx["seed"], c, 2 ** 31, int(length), vocab)
            for c, length in enumerate(traffic["prompt_lengths"])]
    ctx["lap"]("ServingEngine built")
    engine.serve_group(warm, 2)
    ctx["lap"]("first warm-up group (compiles or loads two programs)")
    engine.serve_group(warm, 2)
    ctx["lap"]("second warm-up group")
    batcher = serving.ContinuousBatcher(engine,
                                        **traffic.get("batcher", {}))
    # enough rounds for any window: a round cannot be shorter than its
    # longest answer at a millisecond a step
    longest = max(traffic["output_lengths"])
    rounds = 2 + int(ctx["seconds"] / (longest * 1e-3))
    plan = schedule(traffic, ctx["seed"], rounds)
    # round 0's prompts are made now and each later one by `drive`,
    # ahead of its submission: a client that draws its prompt while it
    # submits spreads a round's submissions towards the batcher's delay,
    # and a round that outlasts it splits into two groups
    ready = {(c, 0): planned_prompt(ctx["seed"], plan, vocab, c, 0)
             for c in range(n)}
    return {"net": net, "ready": ready, "engine": engine, "batcher": batcher,
            "serving": serving, "vocab": vocab,
            "plan": plan, "n": n,
            "pinned": serving.trace_count()}


def arrays(state):
    """The engine's weights and a cache as it allocates one."""
    engine = state["engine"]
    return list(engine._weights) + list(engine.init_cache(1))


# -- the measured window -------------------------------------------------------

def _wait(pending, timeout):
    """The futures of ``pending`` that are done, once one is or
    ``timeout`` seconds (None: no limit) have passed."""
    done, _ = concurrent.futures.wait(
        pending, timeout=timeout,
        return_when=concurrent.futures.FIRST_COMPLETED)
    return done


def rounds_split(records):
    """How many rounds had their requests served in more than one
    group.  The requests of a group carry the group's own bucket and its
    two clock readings, so two groups never agree in all three."""
    groups = {}
    for rec in records:
        groups.setdefault(rec["round"], set()).add(
            (tuple(rec["bucket"]), rec["prefill_us"], rec["collect_us"]))
    return sum(len(g) > 1 for g in groups.values())


def drive(state, window, ctx):
    batcher, plan, n = state["batcher"], state["plan"], state["n"]
    seed, vocab, ready = ctx["seed"], state["vocab"], state["ready"]
    records, failed, drawn_late = [], 0, 0
    pending = {}
    turn = [0] * n
    live = set(range(n))        # clients the window has not yet refused

    def wanted():
        """(client, round) of every next prompt not yet made."""
        return [(c, turn[c]) for c in live
                if turn[c] < len(plan) and (c, turn[c]) not in ready]

    def send(client):
        nonlocal drawn_late
        r = turn[client]
        if r >= len(plan) or not window.submit(client, group=r):
            live.discard(client)
            return
        prompt = ready.pop((client, r), None)
        if prompt is None:
            drawn_late += 1
            prompt = planned_prompt(seed, plan, vocab, client, r)
        new = int(plan[r, client, 1])
        fut = batcher.submit(prompt, new)
        pending[fut] = (client, r, prompt, new)
        turn[client] += 1

    for client in range(n):
        send(client)
    attempted = 0
    while pending:
        want = wanted()
        done = _wait(pending, QUIET_S if want else None)
        if not done:
            for c, r in want:
                if any(fut.done() for fut in pending):
                    break       # an answer waits: its client goes first
                ready[c, r] = planned_prompt(seed, plan, vocab, c, r)
            continue
        for fut in done:
            client, r, prompt, new = pending.pop(fut)
            attempted += 1
            try:
                rec = fut.result()
            except Exception as exc:     # a failed request is counted
                window.abandon(client)
                failed += 1
                ctx["log"](f"request failed: {exc!r}")
            else:
                took = window.complete(client, len(rec["tokens"]))
                rec = dict(rec)
                rec.update(client=client, round=r, prompt=prompt,
                           asked=new, latency_s=took)
                records.append(rec)
            send(client)
    retraced = state["serving"].trace_count() - state["pinned"]
    return {"records": records, "attempted": attempted, "failed": failed,
            "faults": {"retraces_in_window": retraced},
            "watched": {"rounds_split": rounds_split(records),
                        "prompts_drawn_late": drawn_late}}


def close(state):
    state["batcher"].close()


# -- correct -------------------------------------------------------------------

def _sample(records, seed, min_tokens):
    """The longest finished request and others drawn from the seed, up
    to ``min_tokens`` served tokens and four requests at least."""
    order = sorted(range(len(records)), key=lambda i: (
        -(len(records[i]["prompt"]) + len(records[i]["tokens"])),
        records[i]["round"], records[i]["client"]))
    rng = np.random.default_rng([int(seed), 3])
    rest = [order[i] for i in rng.permutation(len(order) - 1) + 1] \
        if len(order) > 1 else []
    picked, tokens = [], 0
    for i in order[:1] + rest:
        picked.append(records[i])
        tokens += len(records[i]["tokens"])
        if tokens >= min_tokens and len(picked) >= 4:
            break
    return picked


def _gaps(cell, seed, sample, prods):
    """For each product in ``prods`` the reference's logits at every
    served position of ``sample``; yields (reference logits, logits by
    the other products) one request at a time."""
    import jax.numpy as jnp

    from benchmark import weights

    ref, config = cell["reference"], cell["config"]
    params = weights.make(seed, ref.param_spec(config),
                          config["program"]["dtype"])
    window = config["n_positions"]
    for rec in sample:
        n_p, toks = len(rec["prompt"]), np.asarray(rec["tokens"])
        ids = np.zeros((1, window), np.int32)   # one compiled length
        full = np.concatenate([rec["prompt"], toks[:-1]])
        ids[0, :len(full)] = full
        at = slice(n_p - 1, n_p - 1 + len(toks))
        yield toks, [np.asarray(ref.logits(params, jnp.asarray(ids),
                                           config, p)[0, at])
                     for p in prods]


def _widest_gap(result, ctx, prods, token_at):
    """The widest gap, over a seeded sample of finished requests, by
    which the logit of ``token_at(served tokens, *logits)`` lies below
    the reference's best at that position."""
    cell = ctx["cell"]
    sample = _sample(result["records"], ctx["seed"],
                     cell["traffic"]["check_tokens"])
    widest, n = 0.0, 0
    for toks, logits in _gaps(cell, ctx["seed"], sample, prods):
        ref = logits[0]
        gap = ref.max(axis=-1) - ref[np.arange(len(toks)),
                                     token_at(toks, *logits)]
        widest, n = max(widest, float(gap.max())), n + len(toks)
    ctx["log"](f"compared {n} served tokens of {len(sample)} requests")
    return [{"name": "served_token_logit_gap_max", "value": widest,
             "limit": cell["traffic"]["limits"]["served_token_logit_gap_max"]}]


def verify(state, result, ctx):
    """The served (greedy) tokens under the float32 reference."""
    return _widest_gap(result, ctx, [ctx["cell"]["reference"].product],
                       lambda toks, ref: toks)


def control(state, result, ctx):
    """The same comparison with the reference in float8 in the program's
    place: at each position of the same prompts and tokens, the token
    the lower precision puts first."""
    ref = ctx["cell"]["reference"]
    return _widest_gap(result, ctx, [ref.product, ref.low_precision],
                       lambda toks, full, low: low.argmax(axis=-1))
