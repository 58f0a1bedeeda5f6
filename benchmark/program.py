"""Building the system under test from a configuration file.

The configuration's ``program`` group names the model-zoo constructor
with its keyword arguments and the type it runs in.  The parameters are
then overwritten, leaf by leaf name, with the seeded values of
`weights.make` for the reference's own list of leaves: one jitted call
on the device, nothing drawn on the host.
"""

import importlib


def resolve(dotted):
    mod, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), attr)


def context(platform):
    import mxnet_tpu as mx

    return mx.cpu(0) if platform == "cpu" else mx.tpu(0)


def match_leaves(spec, names):
    """{leaf: parameter name}.  Below the prefix all parameter names
    share (the net's own, which counts instances), a leaf is a
    parameter's whole name or else the suffix of exactly one; no
    parameter is left over."""
    import os

    common = os.path.commonprefix(list(names))
    common = common[:common.rfind("_") + 1]
    short = {n[len(common):]: n for n in names}
    out = {}
    for leaf, _, _ in spec:
        if leaf in short:
            out[leaf] = short[leaf]
            continue
        hits = [n for s, n in short.items() if s.endswith("_" + leaf)]
        if len(hits) != 1:
            raise ValueError(f"program: leaf {leaf!r} matches {hits}")
        out[leaf] = hits[0]
    left = set(names) - set(out.values())
    if left:
        raise ValueError(f"program: parameters without a seeded value: "
                         f"{sorted(left)}")
    return out


def build_net(cell, seed, platform):
    """(net, {leaf: Parameter}) with seeded weights on the device."""
    import mxnet_tpu as mx

    from benchmark import weights

    config = cell["config"]
    prog = config["program"]
    net = resolve(prog["constructor"])(**prog["kwargs"])
    net.initialize(init=mx.init.Zero(), ctx=context(platform))
    net.cast(prog["dtype"])
    spec = cell["reference"].param_spec(config)
    params = net.collect_params()
    leaves = match_leaves(spec, list(params.keys()))
    values = weights.make(seed, spec, prog["dtype"])
    out = {}
    # under the context, so that a parameter whose shape was deferred is
    # materialised on the device too, not on the default (host) context
    with context(platform):
        for leaf, shape, _ in spec:
            p = params[leaves[leaf]]
            if p.shape is not None and tuple(p.shape) != tuple(shape) \
                    and all(s > 0 for s in p.shape):
                raise ValueError(f"program: {leaves[leaf]} is {p.shape}, "
                                 f"the reference's {leaf} is {shape}")
            p.set_data(values[leaf])
            out[leaf] = p
    return net, out
