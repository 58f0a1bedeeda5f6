"""Useful output tokens over the rows x steps the engine ran: each group
decodes every row of its batch bucket for as many steps as its longest
answer.  Records of one group share ``t_decode0``."""


def read(run, params):
    groups = {}
    for rec in run["records"]:
        if "bucket" not in rec or "tokens" not in rec:
            continue
        g = groups.setdefault(rec["t_decode0"], [rec["bucket"][0], 0, 0])
        g[1] = max(g[1], len(rec["tokens"]))
        g[2] += len(rec["tokens"])
    ran = sum(rows * steps for rows, steps, _ in groups.values())
    if not ran:
        return None
    return 100.0 * sum(g[2] for g in groups.values()) / ran
