"""Model zoo tests (reference: tests/python/unittest/test_gluon_model_zoo.py).

Here we keep CPU-mesh costs sane: construct every family, forward the
cheap ones.
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import vision


def test_get_model_registry():
    with pytest.raises(ValueError):
        vision.get_model("no_such_model")
    net = vision.get_model("resnet18_v1", classes=10)
    assert net is not None


def test_resnet18_thumbnail_forward():
    net = vision.get_model("resnet18_v1", classes=10, thumbnail=True)
    net.initialize(init=mx.init.Xavier())
    out = net(mx.nd.random_normal(shape=(2, 3, 32, 32)))
    assert out.shape == (2, 10)


def test_resnet18_v2_thumbnail_forward():
    net = vision.get_model("resnet18_v2", classes=10, thumbnail=True)
    net.initialize(init=mx.init.Xavier())
    out = net(mx.nd.random_normal(shape=(2, 3, 32, 32)))
    assert out.shape == (2, 10)


def test_resnet50_structure():
    net = vision.resnet50_v1(classes=1000)
    net.initialize(init=mx.init.Xavier())
    # materialize deferred shapes with a tiny spatial input: conv stack
    # accepts any spatial size >= 32
    out = net(mx.nd.random_normal(shape=(1, 3, 64, 64)))
    assert out.shape == (1, 1000)
    n_params = sum(int(np.prod(p.shape))
                   for p in net.collect_params().values())
    # ResNet-50 has ~25.6M parameters
    assert 24e6 < n_params < 27e6, n_params


def test_resnet_nhwc_matches_nchw(tmp_path):
    """layout='NHWC' ResNet (the BASELINE.md layout experiment) computes
    the SAME function as the NCHW model: parameters are layout-portable
    (weights stay OIHW), so an NCHW checkpoint loads into the NHWC
    variant and the outputs match on transposed input — fwd and grads."""
    from mxnet_tpu import autograd

    net = vision.get_model("resnet18_v1", classes=4, thumbnail=True)
    net.initialize(init=mx.init.Xavier())
    x = mx.nd.random_normal(shape=(2, 3, 32, 32))
    x.attach_grad()
    with autograd.record():
        out = net(x)
        loss = (out * out).sum()
    loss.backward()
    ref, gref = out.asnumpy(), x.grad.asnumpy()

    f = str(tmp_path / "r18.params")
    net.save_parameters(f)
    net2 = vision.get_model("resnet18_v1", classes=4, thumbnail=True,
                            layout="NHWC")
    net2.load_parameters(f)
    x2 = mx.nd.array(np.transpose(x.asnumpy(), (0, 2, 3, 1)))
    x2.attach_grad()
    with autograd.record():
        out2 = net2(x2)
        loss2 = (out2 * out2).sum()
    loss2.backward()
    np.testing.assert_allclose(out2.asnumpy(), ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.transpose(x2.grad.asnumpy(), (0, 3, 1, 2)), gref,
        rtol=1e-4, atol=1e-4)


def test_resnet50_nhwc_structure():
    """The NHWC config (resnet50_v1 layout='NHWC') builds, forwards
    and keeps the NCHW parameter count."""
    net = vision.resnet50_v1(classes=1000, layout="NHWC")
    net.initialize(init=mx.init.Xavier())
    out = net(mx.nd.random_normal(shape=(1, 64, 64, 3)))
    assert out.shape == (1, 1000)
    n_params = sum(int(np.prod(p.shape))
                   for p in net.collect_params().values())
    assert 24e6 < n_params < 27e6, n_params


def test_mobilenet_forward():
    net = vision.get_model("mobilenet0.25", classes=10)
    net.initialize(init=mx.init.Xavier())
    out = net(mx.nd.random_normal(shape=(1, 3, 64, 64)))
    assert out.shape == (1, 10)


def test_mobilenet_v2_forward():
    net = vision.get_model("mobilenetv2_0.25", classes=10)
    net.initialize(init=mx.init.Xavier())
    out = net(mx.nd.random_normal(shape=(1, 3, 64, 64)))
    assert out.shape == (1, 10)


def test_squeezenet_forward():
    net = vision.get_model("squeezenet1.1", classes=10)
    net.initialize(init=mx.init.Xavier())
    out = net(mx.nd.random_normal(shape=(1, 3, 64, 64)))
    assert out.shape == (1, 10)


def test_densenet_constructs():
    net = vision.densenet121(classes=10)
    assert net is not None


def test_vgg_alexnet_inception_construct():
    assert vision.vgg11(classes=10) is not None
    assert vision.alexnet(classes=10) is not None
    assert vision.inception_v3(classes=10) is not None


def test_model_zoo_save_load(tmp_path):
    net = vision.get_model("resnet18_v1", classes=4, thumbnail=True)
    net.initialize(init=mx.init.Xavier())
    x = mx.nd.random_normal(shape=(1, 3, 32, 32))
    ref = net(x).asnumpy()
    f = str(tmp_path / "r18.params")
    net.save_parameters(f)
    net2 = vision.get_model("resnet18_v1", classes=4, thumbnail=True)
    net2.load_parameters(f)
    np.testing.assert_allclose(net2(x).asnumpy(), ref, rtol=1e-5,
                               atol=1e-6)


def _copy_unstacked_to_scan(pa, pb, eprefix, sprefix, num_layers):
    """Copy an unstacked transformer trunk's per-layer params into a
    scan trunk's (L, ...) stacks — the one home of the *_stack_* naming
    convention both equivalence tests rely on."""
    from mxnet_tpu import nd

    def stack(name):
        return nd.array(np.stack(
            [pa[f"{eprefix}layer{i}_{name}"].data().asnumpy()
             for i in range(num_layers)]))

    for nm in ("qkv_weight", "qkv_bias", "proj_weight", "proj_bias",
               "ffn1_weight", "ffn1_bias", "ffn2_weight", "ffn2_bias"):
        pb[f"{sprefix}{nm.replace('_', '_stack_', 1)}"].set_data(
            stack(nm))
    for li, tag in ((0, "ln1"), (1, "ln2")):
        for wb in ("gamma", "beta"):
            pb[f"{sprefix}{tag}_stack_{wb}"].set_data(nd.array(np.stack(
                [pa[f"{eprefix}layer{i}_layernorm{li}_{wb}"]
                 .data().asnumpy() for i in range(num_layers)])))
    for wb in ("gamma", "beta"):
        final = [n for n in pa
                 if n.startswith(f"{eprefix}layernorm")
                 and n.endswith(wb)]
        pb[f"{sprefix}lnf_{wb}"].set_data(pa[final[0]].data())


def test_scan_transformer_encoder_matches_unstacked():
    """ScanTransformerEncoder (lax.scan trunk) must equal
    TransformerEncoder layer-by-layer math, fwd and grads."""
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd
    from mxnet_tpu.gluon.model_zoo import bert as bz

    rs = np.random.RandomState(0)
    L, U, H = 3, 32, 4
    enc = bz.TransformerEncoder(L, U, H, dropout=0.0)
    enc.initialize(init=mx.init.Xavier())
    senc = bz.ScanTransformerEncoder(L, U, H, dropout=0.0)
    senc.initialize(init=mx.init.Xavier())

    ep = enc.collect_params()
    epre = [n for n in ep if n.endswith("layer0_qkv_weight")][0]
    eprefix = epre[:-len("layer0_qkv_weight")]
    sp = senc.collect_params()
    spre = [n for n in sp if n.endswith("qkv_stack_weight")][0]
    sprefix = spre[:-len("qkv_stack_weight")]
    _copy_unstacked_to_scan(ep, sp, eprefix, sprefix, L)

    x = nd.array(rs.randn(2, 5, U).astype("float32"))
    x2 = nd.array(x.asnumpy())
    x.attach_grad()
    x2.attach_grad()
    with autograd.record():
        y1 = enc(x)
        (y1 * y1).sum().backward()
    with autograd.record():
        y2 = senc(x2)
        (y2 * y2).sum().backward()
    np.testing.assert_allclose(y2.asnumpy(), y1.asnumpy(), atol=2e-5)
    np.testing.assert_allclose(x2.grad.asnumpy(), x.grad.asnumpy(),
                               atol=2e-4)


def test_bert_scan_layers_trains():
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import bert as bz

    net = bz.bert_tiny(dropout=0.0, scan_layers=True, max_length=32)
    net.initialize(init=mx.init.Xavier())
    tr = parallel.ShardedTrainer(
        net, bz.BERTPretrainLoss(), "adamw", {"learning_rate": 1e-3},
        mesh=parallel.data_parallel_mesh(1))
    rs = np.random.RandomState(0)
    ids = mx.nd.array(rs.randint(0, 512, (4, 32)).astype("int32"))
    mlm = np.where(rs.rand(4, 32) < 0.2,
                   rs.randint(0, 512, (4, 32)), -1).astype("int32")
    nsp = rs.randint(0, 2, (4,)).astype("int32")
    losses = [float(np.asarray(
        tr.step(ids, (mx.nd.array(mlm), mx.nd.array(nsp)))._data,
        dtype=np.float32)) for _ in range(4)]
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_transformer_seq2seq_overfits_copy_and_decodes():
    """NMT-family Transformer: causal decoder + cross-attention learn a
    fixed copy batch to ~zero loss; greedy decode reproduces it."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon.model_zoo import transformer as tfm

    rs = np.random.RandomState(0)
    V, B, T = 20, 16, 8
    net = tfm.transformer_tiny(V, V, dropout=0.0, max_length=16)
    net.initialize(init=mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-2})
    loss_fn = tfm.LabelSmoothedCELoss(smoothing=0.0)
    src_np = rs.randint(3, V, (B, T)).astype("float32")
    tgt_in_np = np.concatenate([np.full((B, 1), 1.0),
                                src_np[:, :-1]], axis=1)
    src = nd.array(src_np)
    tgt_in = nd.array(tgt_in_np)
    labels = nd.array(src_np)
    for _ in range(150):
        with autograd.record():
            loss = loss_fn(net(src, tgt_in), labels)
        loss.backward()
        trainer.step(B)
    final = float(nd.array(loss).asnumpy())
    assert final < 0.05, final
    out = net.greedy_decode(src, bos_id=1, eos_id=2, max_len=T + 1)
    acc = (out[:, 1:T + 1] == src_np.astype(np.int32)).mean()
    assert acc > 0.95, acc


def test_transformer_decoder_is_causal():
    """Changing a future target token must not change earlier logits."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd
    from mxnet_tpu.gluon.model_zoo import transformer as tfm

    rs = np.random.RandomState(1)
    net = tfm.transformer_tiny(12, 12, dropout=0.0, max_length=8)
    net.initialize(init=mx.init.Xavier())
    src = nd.array(rs.randint(3, 12, (2, 6)).astype("float32"))
    tgt = rs.randint(3, 12, (2, 6)).astype("float32")
    with autograd.predict_mode():
        l1 = net(src, nd.array(tgt)).asnumpy()
        tgt2 = tgt.copy()
        tgt2[:, -1] = (tgt2[:, -1] % 9) + 3  # perturb the LAST token
        l2 = net(src, nd.array(tgt2)).asnumpy()
    np.testing.assert_allclose(l1[:, :-1], l2[:, :-1], atol=1e-5)
    assert np.abs(l1[:, -1] - l2[:, -1]).max() > 1e-4


def test_transformer_beam_search_beats_or_matches_greedy():
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon.model_zoo import transformer as tfm

    rs = np.random.RandomState(0)
    V, B, T = 20, 8, 6
    net = tfm.transformer_tiny(V, V, dropout=0.0, max_length=16)
    net.initialize(init=mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-2})
    loss_fn = tfm.LabelSmoothedCELoss(smoothing=0.0)
    src_np = rs.randint(3, V, (B, T)).astype("float32")
    tgt_in = np.concatenate([np.full((B, 1), 1.0),
                             src_np[:, :-1]], axis=1)
    src = nd.array(src_np)
    for _ in range(120):
        with autograd.record():
            loss = loss_fn(net(src, nd.array(tgt_in)), nd.array(src_np))
        loss.backward()
        trainer.step(B)
    out, sc = tfm.beam_search(net, src, bos_id=1, eos_id=2, beam_size=3,
                              max_len=T + 1)
    acc = (out[:, 1:T + 1] == src_np.astype(np.int32)).mean()
    assert acc > 0.9, acc
    assert np.isfinite(sc).all()


def test_scan_encoder_remat_identical_grads():
    """remat=True recomputes layer activations in the backward; grads
    must be bit-identical to the non-remat scan."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import scan_transformer_encoder

    rs = np.random.RandomState(0)
    L, U, H = 3, 16, 2
    args = [jnp.asarray(a.astype(np.float32)) for a in (
        rs.randn(2, 4, U),
        rs.randn(L, 3 * U, U) * 0.1, rs.randn(L, 3 * U) * 0.1,
        rs.randn(L, U, U) * 0.1, rs.randn(L, U) * 0.1,
        rs.randn(L, 4 * U, U) * 0.1, rs.randn(L, 4 * U) * 0.1,
        rs.randn(L, U, 4 * U) * 0.1, rs.randn(L, U) * 0.1,
        np.ones((L, U)), np.zeros((L, U)),
        np.ones((L, U)), np.zeros((L, U)),
        np.ones(U), np.zeros(U))]

    def loss(remat):
        def f(x):
            out = scan_transformer_encoder(
                x, *args[1:], num_heads=H, dropout=0.0, remat=remat)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return jax.grad(f)(args[0])

    g0 = np.asarray(loss(False))
    g1 = np.asarray(loss(True))
    np.testing.assert_array_equal(g0, g1)


def test_gpt_trains_causal_and_generates():
    """Decoder-only LM family: gpt_tiny learns the next-token pattern,
    attention is provably causal (future-token edits cannot change past
    logits), and greedy generate() continues the learned sequence."""
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon.model_zoo import gpt

    net = gpt.gpt_tiny()
    net.initialize(init=mx.init.Xavier())
    loss_fn = gpt.GPTLMLoss()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 3e-3})
    rs = np.random.RandomState(0)
    seq = (np.cumsum(np.ones((8, 32)), axis=1)
           + rs.randint(0, 16, (8, 1))) % 16        # next = (t + 1) % 16
    ids = nd.array(seq.astype(np.float32))
    losses = []
    for _ in range(30):
        with autograd.record():
            loss = loss_fn(net(ids), ids)
        loss.backward()
        tr.step(8)
        losses.append(float(loss.asnumpy()))
    assert losses[-1] < 0.5 * losses[0], losses[::10]

    ids2 = seq.copy()
    ids2[:, 20] = (ids2[:, 20] + 7) % 16
    l1 = net(nd.array(seq.astype(np.float32))).asnumpy()
    l2 = net(nd.array(ids2.astype(np.float32))).asnumpy()
    np.testing.assert_allclose(l1[:, :20], l2[:, :20], atol=1e-5)
    assert not np.allclose(l1[:, 20:], l2[:, 20:], atol=1e-5)

    out = gpt.generate(net, ids[:2, :8], max_new_tokens=4).asnumpy()
    expect = [(seq[0, 7] + k + 1) % 16 for k in range(4)]
    np.testing.assert_array_equal(out[0, 8:12], expect)


def test_gpt_scan_matches_unstacked():
    """scan_layers=True GPT (one scanned causal layer) == the unstacked
    trunk given the same parameters — fwd logits match."""
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo import gpt

    L = 2
    a = gpt.gpt_tiny(scan_layers=False)
    a.initialize(init=mx.init.Xavier())
    b = gpt.gpt_tiny(scan_layers=True)
    b.initialize(init=mx.init.Xavier())
    ids = nd.array(np.random.RandomState(1)
                   .randint(0, 128, (2, 16)).astype(np.float32))
    a(ids)
    b(ids)

    pa, pb = dict(a.collect_params()), dict(b.collect_params())
    epre = [n for n in pa if n.endswith("layer0_qkv_weight")][0]
    eprefix = epre[:-len("layer0_qkv_weight")]
    spre = [n for n in pb if n.endswith("qkv_stack_weight")][0]
    sprefix = spre[:-len("qkv_stack_weight")]
    _copy_unstacked_to_scan(pa, pb, eprefix, sprefix, L)
    for nm in ("tok_embed_weight", "pos_embed_weight"):
        src_key = [k for k in pa if k.endswith(nm)][0]
        dst_key = [k for k in pb if k.endswith(nm)][0]
        pb[dst_key].set_data(pa[src_key].data())

    np.testing.assert_allclose(b(ids).asnumpy(), a(ids).asnumpy(),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("scan", [False, True])
def test_gpt_cached_decoder_matches_recompute(scan):
    """KV-cache incremental decoding (the family's serving step, one
    jitted program walked by the host) produces byte-identical tokens
    to the full-recompute generate() — both trunk variants."""
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo import gpt

    net = gpt.gpt_tiny(scan_layers=scan)
    net.initialize(init=mx.init.Xavier())
    ids = nd.array(np.random.RandomState(0)
                   .randint(0, 128, (2, 6)).astype(np.float32))
    net(ids)
    ref = gpt.generate(net, ids, max_new_tokens=5).asnumpy()
    dec = gpt.CachedDecoder(net).decode(ids, max_new_tokens=5).asnumpy()
    np.testing.assert_array_equal(ref, dec)


def test_gpt_cached_decoder_runs_the_serving_program():
    """CachedDecoder has no layer body of its own: its jitted step is
    GPTDecoderProgram.step, whose one scan carries the (L, B, H, W, Dh)
    cache pair and scans nothing of that shape (a body that scans the
    cache in and stacks it out copies every layer each way), and its
    walk gives what a ServingEngine's compiled programs give for the
    same model, group and prefill bucket."""
    import jax

    from mxnet_tpu import nd, serving
    from mxnet_tpu.gluon.model_zoo import gpt
    from mxnet_tpu.test_utils import jaxpr_loops, serving_host_walk

    net = gpt.gpt_tiny(scan_layers=True, num_layers=3)
    net.initialize(init=mx.init.Xavier())
    B, T0, N = 2, 6, 5
    seed = np.random.RandomState(4).randint(0, 128, (B, T0))
    ids = nd.array(seed.astype(np.float32))
    net(ids)
    dec = gpt.CachedDecoder(net)

    program = dec._program
    ck, cv = program.init_cache(B)
    stack = tuple(ck.shape)
    assert stack[:2] == (3, B) and len(stack) == 5
    ints = np.zeros(B, np.int32)
    jaxpr = jax.make_jaxpr(dec._step_fn)(
        program.weights(), (ck, cv), ints, ints, np.zeros((B, 1), np.int32))

    found = list(jaxpr_loops(jaxpr.jaxpr))
    assert [e.primitive.name for e in found] == ["scan"]
    scan = found[0]
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    carried = [tuple(v.aval.shape)
               for v in scan.invars[n_consts:n_consts + n_carry]]
    assert carried.count(stack) == 2, carried
    for v in scan.invars[n_consts + n_carry:] + scan.outvars[n_carry:]:
        assert tuple(v.aval.shape) != stack, v.aval

    toks, lg = dec.decode(ids, max_new_tokens=N, return_logits=True)
    eng = serving.ServingEngine(net, batch_buckets=(B,))
    want_t, want_lg = serving_host_walk(eng, seed.tolist(), N)
    _assert_decode_equiv(
        np.concatenate([seed, want_t], axis=1), want_lg.swapaxes(0, 1),
        toks.asnumpy(), lg, T0=T0)


def test_gpt_cached_decoder_tensor_parallel():
    """tp-sharded serving: CachedDecoder(mesh=) shards heads, the KV
    cache, and the FFN hidden dim over the tp axis (Megatron rules,
    GSPMD collectives) and produces the same tokens as the
    single-device cached decoder."""
    import jax
    from jax.sharding import Mesh

    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo import gpt

    net = gpt.gpt_tiny(scan_layers=True)
    net.initialize(init=mx.init.Xavier())
    ids = nd.array(np.random.RandomState(1)
                   .randint(0, 128, (2, 6)).astype(np.float32))
    net(ids)
    ref_t, ref_lg = gpt.CachedDecoder(net).decode(
        ids, max_new_tokens=5, return_logits=True)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    tp_t, tp_lg = gpt.CachedDecoder(net, mesh=mesh).decode(
        ids, max_new_tokens=5, return_logits=True)
    _assert_decode_equiv(ref_t.asnumpy(), ref_lg, tp_t.asnumpy(), tp_lg,
                         T0=ids.shape[1])


def test_gpt_cached_decoder_bf16_serving():
    """dtype='bfloat16' puts the big tensors (weight stacks, embed
    tables, KV cache) in bf16 HBM while accumulating f32 — logits stay
    within bf16 tolerance of the f32 decoder, also combined with tp."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo import gpt

    net = gpt.gpt_tiny(scan_layers=True)
    net.initialize(init=mx.init.Xavier())
    ids = nd.array(np.random.RandomState(2)
                   .randint(0, 128, (2, 6)).astype(np.float32))
    net(ids)
    _, ref_lg = gpt.CachedDecoder(net).decode(
        ids, max_new_tokens=3, return_logits=True)
    dec = gpt.CachedDecoder(net, dtype="bfloat16")
    toks, lg = dec.decode(ids, max_new_tokens=3, return_logits=True)
    assert toks.shape == (2, 9)
    scale = np.abs(ref_lg[0]).max()
    np.testing.assert_allclose(lg[0], ref_lg[0], atol=0.05 * scale)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    _, lg_tp = gpt.CachedDecoder(net, mesh=mesh, dtype="bfloat16").decode(
        ids, max_new_tokens=3, return_logits=True)
    np.testing.assert_allclose(lg_tp[0], ref_lg[0], atol=0.05 * scale)
    # the cache really is bf16 (the HBM claim)
    assert dec._program.init_cache(1)[0].dtype == jnp.bfloat16


def _assert_decode_equiv(ref_t, ref_lg, tp_t, tp_lg, T0):
    """Greedy tokens should match; if argmax flips, it is legitimate
    ONLY inside float32 rounding noise — the sharded partial-sum
    all-reduce associates reductions differently, so the contract is
    logits-to-rounding, tokens-in-practice."""
    np.testing.assert_allclose(tp_lg[0], ref_lg[0], rtol=2e-4, atol=1e-5)
    if np.array_equal(ref_t, tp_t):
        return
    j = int(np.argwhere((ref_t != tp_t).any(axis=0))[0, 0]) - T0
    np.testing.assert_allclose(
        tp_lg[j], ref_lg[j], rtol=2e-4, atol=1e-5,
        err_msg=f"tokens diverged at step {j} with logits beyond "
                "rounding tolerance")


def test_gpt_flash_attention_trains():
    """The causal LM with attention_impl='flash' (interpret mode on
    CPU): the Pallas causal kernel inside the full training step."""
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon.model_zoo import gpt

    net = gpt.gpt_tiny(attention_impl="flash", scan_layers=True)
    net.initialize(init=mx.init.Xavier())
    loss_fn = gpt.GPTLMLoss()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 3e-3})
    rs = np.random.RandomState(0)
    seq = (np.cumsum(np.ones((4, 32)), axis=1)
           + rs.randint(0, 16, (4, 1))) % 16
    ids = nd.array(seq.astype(np.float32))
    losses = []
    for _ in range(10):
        with autograd.record():
            loss = loss_fn(net(ids), ids)
        loss.backward()
        tr.step(4)
        losses.append(float(loss.asnumpy()))
    assert losses[-1] < losses[0], losses


def test_gpt_beam_generate():
    """Beam search for the decoder-only family (shared beam_loop core):
    on a trained deterministic next-token pattern, beam-1 equals greedy
    generate() and wider beams score at least as well."""
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon.model_zoo import gpt

    net = gpt.gpt_tiny()
    net.initialize(init=mx.init.Xavier())
    loss_fn = gpt.GPTLMLoss()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 3e-3})
    rs = np.random.RandomState(0)
    seq = (np.cumsum(np.ones((8, 32)), axis=1)
           + rs.randint(0, 16, (8, 1))) % 16
    ids = nd.array(seq.astype(np.float32))
    for _ in range(30):
        with autograd.record():
            loss = loss_fn(net(ids), ids)
        loss.backward()
        tr.step(8)

    seed = ids[:2, :8]
    greedy = gpt.generate(net, seed, max_new_tokens=4).asnumpy()
    b1, s1 = gpt.beam_generate(net, seed, max_new_tokens=4, beam_size=1)
    np.testing.assert_array_equal(b1.asnumpy(), greedy)
    b4, s4 = gpt.beam_generate(net, seed, max_new_tokens=4, beam_size=4)
    assert (s4 >= s1 - 1e-5).all(), (s1, s4)
    # on a learned deterministic pattern the wide beam agrees too
    np.testing.assert_array_equal(b4.asnumpy(), greedy)


def test_vit_forward_and_trains():
    """VisionTransformer: patchify + scanned pre-LN trunk + cls head;
    hybridized training drops loss; scan and per-layer trunks agree
    in architecture (forward shapes)."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    np.random.seed(0)
    net = vision.get_model("vit_tiny")
    net.initialize(init=mx.init.Xavier())
    x = mx.nd.array(np.random.RandomState(0).randn(2, 3, 32, 32)
                    .astype(np.float32))
    assert net(x).shape == (2, 10)
    unscanned = vision.vit_tiny(scan_layers=False)
    unscanned.initialize(init=mx.init.Xavier())
    assert unscanned(x).shape == (2, 10)
    # deploy path: shape-free hybrid_forward must trace symbolically
    import os
    import tempfile

    net.hybridize()
    net(x)
    with autograd.predict_mode():
        ref = net(x)
    d = tempfile.mkdtemp()
    net.export(os.path.join(d, "vit"))
    sb = gluon.SymbolBlock.imports(
        os.path.join(d, "vit-symbol.json"), ["data"],
        os.path.join(d, "vit-0000.params"))
    np.testing.assert_allclose(sb(x).asnumpy(), ref.asnumpy(),
                               atol=1e-5)
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "adamw",
                       {"learning_rate": 1e-3})
    lf = gluon.loss.SoftmaxCrossEntropyLoss()
    y = mx.nd.array(np.array([1.0, 7.0], np.float32))
    first = last = None
    for _ in range(10):
        with autograd.record():
            l = lf(net(x), y)
        l.backward()
        tr.step(2)
        v = float(l.mean().asnumpy())
        first = v if first is None else first
        last = v
    assert last < first, (first, last)


def test_gpt_trunk_lora_finetuning():
    """Built-in trunk LoRA (scan_transformer_encoder qkv adapters):
    rank-r model with copied base params starts EXACTLY equal (B=0),
    freeze_for_lora leaves only adapters trainable, loss drops, frozen
    stacks don't move."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon.contrib import freeze_for_lora
    from mxnet_tpu.gluon.model_zoo import gpt

    mx.random.seed(0)
    np.random.seed(0)
    base = gpt.gpt_tiny(scan_layers=True, dropout=0.0)
    base.initialize(init=mx.init.Xavier())
    ids = mx.nd.array(np.random.RandomState(0)
                      .randint(0, 100, (2, 16)).astype(np.float32))
    ref = base(ids).asnumpy()

    lnet = gpt.gpt_tiny(scan_layers=True, dropout=0.0, lora_rank=4,
                        lora_alpha=8)
    lnet.initialize(init=mx.init.Xavier())
    bmap = {n.split("_", 1)[1]: p
            for n, p in base.collect_params().items()}
    for n, p in lnet.collect_params().items():
        key = n.split("_", 1)[1]
        if "lora" not in n and key in bmap:
            p.set_data(bmap[key].data())
    np.testing.assert_allclose(lnet(ids).asnumpy(), ref, rtol=2e-5,
                               atol=2e-5)

    n_train, n_total = freeze_for_lora(lnet)
    assert n_train < 0.1 * n_total, (n_train, n_total)
    tr = gluon.Trainer(lnet.collect_params(), "adam",
                       {"learning_rate": 5e-3})
    lf = gpt.GPTLMLoss()
    frozen = {n: p.data().asnumpy().copy()
              for n, p in lnet.collect_params().items()
              if p.grad_req == "null"}
    first = last = None
    for _ in range(8):
        with autograd.record():
            l = lf(lnet(ids), ids)
        l.backward()
        tr.step(2)
        v = float(l.asnumpy())
        first = v if first is None else first
        last = v
    assert last < first, (first, last)
    for n, p in lnet.collect_params().items():
        if p.grad_req == "null":
            np.testing.assert_array_equal(p.data().asnumpy(), frozen[n])
    # non-scan + lora must raise (adapters live in the scanned trunk)
    with pytest.raises(ValueError):
        gpt.GPTModel(vocab_size=100, units=32, num_layers=2,
                     num_heads=2, scan_layers=False, lora_rank=2)


def test_bert_trunk_lora_wires():
    """BERT family forwards lora_rank to the scanned trunk; non-scan
    raises; freeze leaves only adapter params trainable."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.contrib import freeze_for_lora
    from mxnet_tpu.gluon.model_zoo import bert

    net = bert.bert_tiny(scan_layers=True, dropout=0.0, lora_rank=2)
    net.initialize(init=mx.init.Xavier())
    ids = mx.nd.array(np.random.RandomState(0)
                      .randint(0, 200, (2, 16)).astype(np.float32))
    net(ids)
    n_train, n_total = freeze_for_lora(net)
    assert 0 < n_train < 0.05 * n_total
    with pytest.raises(ValueError):
        bert.bert_tiny(lora_rank=2)  # scan_layers=False default


def test_ssd_export_roundtrip(tmp_path):
    """SSD exports symbolically (shape-free head reshapes) and
    SymbolBlock round-trips all three outputs."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon.model_zoo import SSD

    mx.random.seed(0)
    np.random.seed(0)
    net = SSD(num_classes=2)
    net.initialize(init=mx.init.Xavier())
    net.hybridize()
    x = mx.nd.array(np.random.RandomState(0).randn(1, 3, 64, 64)
                    .astype(np.float32))
    net(x)
    with autograd.predict_mode():
        ref = net(x)
    net.export(str(tmp_path / "ssd"))
    sb = gluon.SymbolBlock.imports(
        str(tmp_path / "ssd-symbol.json"), ["data"],
        str(tmp_path / "ssd-0000.params"))
    out = sb(x)
    for o, r in zip(out, ref):
        np.testing.assert_allclose(o.asnumpy(), r.asnumpy(), atol=1e-5)
