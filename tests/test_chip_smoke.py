"""chip_smoke.py's phase functions, driven tiny on the CPU.

The script itself always demands a TPU (`main`); what tier-1 can hold
still is the control flow of each phase and every check in it, at
`gpt_tiny` size with the Pallas kernels interpreted.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

TINY = chip_smoke.Size(
    model="gpt_tiny", batch=4, steps=5, sharded_steps=2,
    kernel_shapes=((2, 2, 64, 16, False), (1, 2, 128, 16, True)),
    sync=(256, 8, 4),
    batch_buckets=(1, 2), prefill_floor=32,
    prompt_lens=(3, 9, 20, 40, 33, 5), new_tokens=4)


def test_main_refuses_a_cpu(capsys):
    """No flag or variable relaxes it: without a TPU the run fails in
    the device phase and prints no result line."""
    with pytest.raises(chip_smoke.SmokeError, match="'tpu' required"):
        chip_smoke.main()
    out = capsys.readouterr().out
    assert "[device] platform=cpu" in out
    assert '"ok"' not in out


def test_device_phase_reports_what_jax_reports():
    import jax

    info = chip_smoke.phase_device("cpu")
    assert info == {"platform": "cpu",
                    "kind": jax.devices()[0].device_kind,
                    "count": len(jax.devices())}
    json.dumps(info)


def test_sync_and_kernel_phases():
    t = chip_smoke.phase_sync(TINY, "cpu")
    assert t["block_until_ready_s"] > 0 and t["readback_s"] > 0
    res = chip_smoke.phase_kernel(TINY, "cpu")
    assert [r["shape"][2] for r in res] == [64, 128]
    # a phase told to expect the chip refuses the interpreter
    with pytest.raises(chip_smoke.SmokeError, match="interpret"):
        chip_smoke.phase_kernel(TINY, "tpu")


def test_train_serve_sharded_phases(mesh8):
    """One model through all three: the captured step (one capture, one
    trace, loss falling from ln(vocab)), the served tokens (coalesced ==
    one by one, zero retraces), and the four-device layouts reproducing
    the single-device step-0 loss."""
    train = chip_smoke.phase_train(TINY, "cpu")
    assert len(train["losses"]) == TINY.steps
    serve = chip_smoke.phase_serve(TINY, "cpu", train.pop("net"))
    assert serve["programs"] == 2 * 3       # (32, 64, decode) x (1, 2)
    sharded = chip_smoke.phase_sharded(TINY, "cpu", train["losses"][0],
                                       train["peak_bytes"])
    assert [s["mode"] for s in sharded] == ["fsdp", "tp"]


def test_serve_mimo_phase(monkeypatch):
    """The second family's phase at a tiny size: the engine's tuple is
    the parameters' own buffers, the four stacks stay where they are,
    coalesced == alone, a repeat is identical, and the packed prefill's
    counters are the prompts' own sums (two rows a chunk in tiles of 8,
    so that the tile loop runs)."""
    from mxnet_tpu.gluon.model_zoo import mimo_v2

    monkeypatch.setattr(mimo_v2, "_TILE", 8)
    full = chip_smoke.mimo_full()
    assert full.kwargs["units"] == 4096 and full.kwargs["window"] == 128
    assert full.new_tokens > 2 * full.kwargs["window"]
    kwargs = dict(vocab_size=96, units=64,
                  layer_types=["full"] + ["window"] * 5 + ["full"],
                  moe_layers=[0, 1, 1, 1, 1, 1, 1], num_heads=4, kv_heads=1,
                  swa_kv_heads=2, qk_dim=24, v_dim=16, rotary_dim=8,
                  window=4, rope_theta=1e7, swa_rope_theta=1e4,
                  hidden_size=96, expert_hidden=32, router_experts=8,
                  experts_per_token=2, experts_held=[0, 4],
                  value_scale=0.707, max_length=64, attn_block=4,
                  prefill_chunk_tokens=32, grad_req="null")
    assert set(kwargs) - {"attn_block", "prefill_chunk_tokens"} \
        <= set(full.kwargs)
    size = chip_smoke.FamilySize(kwargs=kwargs, batch=4, prefill_floor=16,
                               prompt_lens=(3, 4, 13, 16), new_tokens=13)
    out = chip_smoke.phase_serve_mimo(size, "cpu")
    assert out["retraces"] == 0 and out["programs"] == 2
    assert isinstance(mimo_v2.mimo_v2_tiny(), mimo_v2.MiMoV2Model)


def test_serve_keye_phase():
    """The third family's phase at a tiny size: the engine's tuple is the
    parameters' own buffers, the three stacks stay where they are, the
    keys read are counted, coalesced == alone, a repeat is identical,
    and both selections equal the reference's sort."""
    small = chip_smoke.keye_small()
    assert small.kwargs["head_dim"] == 128 and small.prefill_floor \
        == small.kwargs["max_length"]
    kwargs = dict(vocab_size=96, units=64, num_layers=3, num_heads=4,
                  kv_heads=2, head_dim=16, index_heads=2, index_dim=8,
                  topk=8, expert_hidden=32, router_experts=8,
                  experts_per_token=2, experts_held=[2, 4], max_length=64,
                  grad_req="null")
    assert set(kwargs) <= set(small.kwargs)
    size = chip_smoke.FamilySize(kwargs=kwargs, batch=4, prefill_floor=64,
                               prompt_lens=(3, 8, 21, 40), new_tokens=7)
    out = chip_smoke.phase_serve_keye(size, "cpu")
    assert out["retraces"] == 0 and out["programs"] == 2


def test_serve_kimi_phase():
    """The fourth family's phase at a tiny size: the engine's tuple is
    the parameters' own buffers, the one latent stack stays where it is,
    the positions attended to are counted, coalesced == alone (two rows
    a prefill chunk), a repeat is identical."""
    small = chip_smoke.kimi_small()
    assert small.kwargs["kv_rank"] + small.kwargs["rope_dim"] == 320 \
        and small.prefill_floor == small.kwargs["max_length"]
    kwargs = dict(vocab_size=96, units=64, num_layers=3, num_heads=4,
                  q_rank=24, kv_rank=16, nope_dim=16, rope_dim=8, v_dim=12,
                  hidden_size=96, expert_hidden=32, router_experts=8,
                  experts_per_token=2, experts_held=[2, 4], route_scale=2.5,
                  rope_factor=8.0, rope_original_length=8, mscale=1.0,
                  mscale_all_dim=1.0, max_length=64, attn_block=16,
                  token_chunk=16, prefill_chunk_tokens=128, grad_req="null")
    assert set(kwargs) <= set(small.kwargs)
    size = chip_smoke.FamilySize(kwargs=kwargs, batch=4, prefill_floor=64,
                               prompt_lens=(3, 8, 21, 40), new_tokens=7)
    out = chip_smoke.phase_serve_kimi(size, "cpu")
    assert out["retraces"] == 0 and out["programs"] == 2


def test_serve_ouro_phase():
    """The fifth family's phase at a tiny size: the engine's tuple is the
    parameters' own buffers, the two stacks of ``T L`` slots stay where
    they are, the passes, exit steps and positions are counted,
    coalesced == alone, a repeat is identical, and the served logits
    equal the float32 reference's."""
    small = chip_smoke.ouro_small()
    assert small.kwargs["head_dim"] == 128 \
        and small.prefill_floor == small.kwargs["max_length"] == 512
    kwargs = dict(vocab_size=96, units=64, num_layers=2, num_heads=4,
                  kv_heads=4, head_dim=16, hidden_size=96, loop_steps=3,
                  max_length=64, grad_req="null")
    assert set(kwargs) <= set(small.kwargs)
    size = chip_smoke.FamilySize(kwargs=kwargs, batch=4, prefill_floor=64,
                               prompt_lens=(3, 8, 21, 40), new_tokens=7)
    out = chip_smoke.phase_serve_ouro(size, "cpu")
    assert out["retraces"] == 0 and out["programs"] == 2


def test_serve_jamba_phase():
    """The seventh family's phase at a tiny size: the engine's tuple is
    the parameters' own buffers, the two stacks stay where they are, the
    scan's positions (real and walked), the live rows' state updates and
    the attention layer's pairs are counted, coalesced == alone, a
    repeat is identical, a row that wants no token keeps its state and
    tail, and the served logits equal the float32 reference's."""
    small = chip_smoke.jamba_small()
    assert small.kwargs["units"] // small.kwargs["num_heads"] == 128 \
        and small.prefill_floor == 512 and small.kwargs["max_length"] == 704
    kwargs = dict(vocab_size=96, units=64, num_layers=5, num_heads=4,
                  kv_heads=1, hidden_size=96, attn_period=5, attn_offset=2,
                  d_state=16, d_conv=4, dt_rank=4, expand=2, max_length=64,
                  prefill_chunk_tokens=128, grad_req="null")
    assert set(kwargs) <= set(small.kwargs)
    size = chip_smoke.FamilySize(kwargs=kwargs, batch=4, prefill_floor=64,
                               prompt_lens=(1, 3, 21, 40), new_tokens=7)
    out = chip_smoke.phase_serve_jamba(size, "cpu")
    assert out["retraces"] == 0 and out["programs"] == 2


def test_ssd_and_serve_granite_phases():
    """The Mamba-2 kernels' phase, interpreted at tiny tiles (ragged
    lengths, a live subset), and the eighth family's at a tiny size: the
    engine's tuple is the parameters' own buffers, the scan's positions,
    the live rows' updates, the attention layer's pairs and the experts'
    are counted, coalesced == alone, a repeat is identical, a row that
    wants no token keeps its states and tails, and the served logits
    equal the float32 reference's.  The chip's sizes are the published
    widths and the cell's bucket."""
    full, small = chip_smoke.ssd_full(), chip_smoke.granite_small()
    assert (full.H, full.P, full.N, full.S) == (128, 64, 128, 512)
    assert small.kwargs["ssm_head_dim"] == 64 \
        and small.kwargs["d_state"] == 128 and small.prefill_floor == 512 \
        and small.kwargs["max_length"] == 704
    out = chip_smoke.phase_ssd(chip_smoke.SsdSize(
        H=4, P=8, N=16, S=24, lengths=(1, 8, 13, 24), L=2,
        live=(1, 0, 1, 1), tiles=(8, 2, 8)), "cpu")
    assert set(out) == {"scan_float32", "scan_bfloat16", "update"}
    kwargs = dict(vocab_size=96, units=64,
                  layer_types=["mamba", "mamba", "attention", "mamba",
                               "mamba"],
                  num_heads=4, kv_heads=2, ssm_heads=4, ssm_head_dim=8,
                  d_state=16, d_conv=4, expert_hidden=24, shared_hidden=48,
                  router_experts=8, experts_per_token=3,
                  experts_held=(0, 2), embedding_multiplier=12.0,
                  residual_multiplier=0.22, attention_multiplier=0.0625,
                  logits_scaling=4.0, max_length=64,
                  prefill_chunk_tokens=128, grad_req="null")
    assert set(kwargs) <= set(small.kwargs)
    size = chip_smoke.FamilySize(kwargs=kwargs, batch=4, prefill_floor=64,
                               prompt_lens=(1, 3, 21, 40), new_tokens=7)
    out = chip_smoke.phase_serve_granite(size, "cpu")
    assert out["retraces"] == 0 and out["programs"] == 2


def test_moe_grouped_phase(monkeypatch):
    """The grouped product's phase, interpreted at tiny tiles: both
    products of a pass equal ``lax.ragged_dot``'s over layer 1 of the
    stack, a group over three tiles, empty groups and an empty tail
    among the cases, and a pass's way out by the walk of the stream's
    token tiles equals the scatter-add's; no time is taken off the chip.
    The chip's cases are the five expert families' widths, buffers and
    streams, each taken by the kernels."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import moe

    full = chip_smoke.moe_grouped_full()
    assert [c[0] for c in full.cases] == [
        f"{f}.{p}" for f in ("granite", "mimo", "keye", "kimi", "cmda")
        for p in ("decode", "prefill")]
    for _, P, M, F, n, sizes in full.cases:
        assert moe._fits(P, M, F, n, jnp.bfloat16)
        assert len(sizes) == n and 0 < sum(sizes) <= P
    assert [c[0] for c in full.ways_out] == [c[0] for c in full.cases]
    for (_, T, P, M, live, experts, row), case in zip(full.ways_out,
                                                      full.cases):
        assert moe._combine_fits(T, P, M)
        assert (P, M) == (case[1], case[2]) or case[0] == "keye.prefill"
        assert 0 < experts <= live <= P and T % row == 0
    monkeypatch.setattr(moe, "_ROWS", 16)
    monkeypatch.setattr(moe, "_TOKENS", 8)
    out = chip_smoke.phase_moe_grouped(chip_smoke.GroupedSize(cases=(
        ("a", 64, 128, 128, 5, (0, 40, 0, 3, 7)),
        ("b", 32, 256, 128, 3, (32, 0, 0))), reps=2, ways_out=(
        ("c", 64, 32, 128, 21, 3, 16), ("d", 8, 16, 128, 5, 2, 1))), "cpu")
    assert set(out) == {"a", "b", "c.way_out", "d.way_out"}
    assert set(out["a"]) == {"err"}
    assert set(out["c.way_out"]) == {"err", "tiles"}
    assert 0 < out["c.way_out"]["tiles"] <= 8


def test_serve_cmda_phase():
    """The sixth family's phase at a tiny size: the engine's tuple is the
    parameters' own buffers, the four stacks (two rings among them) stay
    where they are, the pairs of each kind of layer are counted (the
    band's on the window layers), coalesced == alone, a repeat is
    identical, and the served logits equal the float32 reference's; the
    phase at the published widths reads the cell's own configuration."""
    small, full = chip_smoke.cmda_small(), chip_smoke.cmda_full()
    assert small.kwargs["head_dim"] == 128 and small.kwargs["window"] == 256
    assert full.kwargs["window"] == 4096 and full.prefill_floor \
        == full.kwargs["max_length"] == 16384
    assert max(full.prompt_lens) + full.new_tokens <= 16384
    kwargs = dict(vocab_size=96, units=64,
                  layer_types=["window", "window", "window", "full"],
                  num_heads=8, kv_heads=2, head_dim=16, window=8,
                  expert_hidden=32, router_experts=8, experts_per_token=2,
                  experts_held=[2, 4], shared_experts=2, max_length=64,
                  token_chunk=16, prefill_chunk_tokens=128,
                  grad_req="null")
    assert set(kwargs) <= set(small.kwargs) | {"dtype"}
    size = chip_smoke.FamilySize(kwargs=kwargs, batch=4, prefill_floor=64,
                               prompt_lens=(3, 8, 21, 40), new_tokens=7)
    out = chip_smoke.phase_serve_cmda(size, "cpu")
    assert out["retraces"] == 0 and out["programs"] == 2
