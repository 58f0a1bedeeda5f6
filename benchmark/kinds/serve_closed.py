"""Traffic kind ``serve_closed``: a closed loop of clients against
``ServingEngine`` + ``ContinuousBatcher``.

The traffic file gives ``clients``, ``batch_buckets``, optionally
``batcher`` (keyword arguments of ``ContinuousBatcher``) and two length
tables of ``clients`` entries each.  In round r the clients between them
hold every entry of each table exactly once, by two permutations drawn
from the seed: the seed moves who asks what and in what company, never
how much is asked.  Sampling is greedy.

One thread drives all clients through the futures the batcher returns.
"""

import concurrent.futures

import numpy as np


# rounds whose prompts set-up makes ahead of the window
READY_ROUNDS = 16


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
    # the first rounds' prompts are made now: a client that draws its
    # prompt while it submits spreads a round's submissions over more
    # than the batcher's delay, and the round splits into two groups
    ready = {(c, r): prompt_ids(ctx["seed"], c, r, int(plan[r, c, 0]), vocab)
             for r in range(min(rounds, READY_ROUNDS)) for c in range(n)}
    return {"net": net, "ready": ready, "engine": engine, "batcher": batcher,
            "serving": serving, "vocab": vocab,
            "plan": plan, "n": n,
            "pinned": serving.trace_count()}


def arrays(state):
    """The engine's weights and a cache as it allocates one."""
    engine = state["engine"]
    return list(engine._weights) + list(engine.init_cache(1))


# -- the measured window -------------------------------------------------------

def drive(state, window, ctx):
    batcher, plan, n = state["batcher"], state["plan"], state["n"]
    seed, vocab = ctx["seed"], state["vocab"]
    records, failed = [], 0
    pending = {}
    turn = [0] * n

    def send(client):
        r = turn[client]
        if r >= len(plan) or not window.submit(client):
            return
        length, new = (int(v) for v in plan[r, client])
        prompt = state["ready"].get((client, r))
        if prompt is None:
            prompt = prompt_ids(seed, client, r, length, vocab)
        fut = batcher.submit(prompt, new)
        pending[fut] = (client, r, prompt, new)
        turn[client] += 1

    for client in range(n):
        send(client)
    attempted = 0
    while pending:
        done, _ = concurrent.futures.wait(
            pending, return_when=concurrent.futures.FIRST_COMPLETED)
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
            "faults": ([f"{retraced} retraces inside the window"]
                       if retraced else [])}


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
