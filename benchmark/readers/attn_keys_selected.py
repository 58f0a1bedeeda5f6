"""The share of the keys attention could read that it did read: the
program's counters ``attn_keys_selected_<phase>`` over
``attn_keys_live_<phase>`` (sums over rows, layers and steps), over the
groups of the window, each counted once.  None where the records carry
no such counters."""


def read(run, params):
    phase = params.get("phase", "decode")
    live_f, sel_f = f"attn_keys_live_{phase}", f"attn_keys_selected_{phase}"
    live = selected = 0
    seen = set()
    for rec in run["records"]:
        if live_f not in rec or sel_f not in rec \
                or rec.get("t_decode0") in seen:
            continue
        seen.add(rec.get("t_decode0"))
        live += rec[live_f]
        selected += rec[sel_f]
    if not live:
        return None
    return 100.0 * selected / live
