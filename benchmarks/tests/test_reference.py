"""The plain reference against the program's ``apply`` at a tiny size,
float32, on the CPU: every mechanism the configurations use."""

import numpy as np
import pytest

import reference


@pytest.mark.parametrize("tc", [
    dict(vocab_size=97, d_model=32, n_heads=4, n_kv_heads=1, n_layers=2,
         d_ff=64, max_len=600, rope=False, dtype="float32"),
    dict(vocab_size=97, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
         d_ff=64, max_len=600, rope=True, rope_theta=999999.4420358813,
         attention_window=48, dtype="float32"),
], ids=["multi_query_learned_positions", "grouped_rotary_windowed_segments"])
def test_reference_equals_apply(tc):
    import jax

    from distkeras_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(**tc)
    params = tfm.init_params(jax.random.key(3), cfg)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 97, 530).astype(np.int32)
    seg = None
    if tc["rope"]:
        seg = (1 + (np.arange(530) >= 200) + (np.arange(530) >= 460)).astype(
            np.int32)
        seg[520:] = 0
    want, _ = tfm.apply(params, tokens[None], cfg,
                        segment_ids=None if seg is None else seg[None])
    hidden = reference.forward(params, tc, tokens, seg)
    got = reference.logits_at(params, hidden, np.arange(530))
    live = slice(None) if seg is None else seg != 0
    np.testing.assert_allclose(got[live], np.asarray(want[0])[live],
                               atol=2e-4, rtol=2e-4)
    if seg is not None:
        rows = np.concatenate([tokens, [5]])[None].astype(np.int32)
        segs = np.concatenate([seg, [0]])[None].astype(np.int32)
        ref = reference.loss(params, tc, rows, segs)
        got = float(tfm.lm_loss(params, rows, cfg, segment_ids=segs))
        assert ref == pytest.approx(got, abs=2e-5)
