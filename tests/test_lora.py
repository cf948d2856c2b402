"""LoRA fine-tuning: merge algebra, frozen-base training, optimizer
state economy, serving composition, and mesh/packing integration."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.models.lora import (
    LoRAConfig,
    lora_init,
    lora_mask,
    lora_merge,
)
from helpers import toy_params


CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=17)


def _rows(rng, n=64):
    return rng.integers(1, 64, (n, 17)).astype(np.int32)


def test_zero_init_merge_is_identity(rng):
    """B = 0 at init: the merged tree equals the base exactly, so step
    0 of a finetune reproduces the pretrained model."""
    params = toy_params(CFG)
    lcfg = LoRAConfig(rank=4, targets=("wq", "wk", "wv", "wo",
                                       "w1", "w2"))
    adapters = lora_init(jax.random.key(1), CFG, lcfg)
    merged = lora_merge(params, adapters, CFG, lcfg)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(merged)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_merge_matches_manual_delta(rng):
    params = toy_params(CFG)
    lcfg = LoRAConfig(rank=3, alpha=6.0, targets=("wq",))
    adapters = lora_init(jax.random.key(1), CFG, lcfg)
    a = np.asarray(rng.normal(size=adapters["attn"]["wq"]["a"].shape),
                   np.float32)
    b = np.asarray(rng.normal(size=adapters["attn"]["wq"]["b"].shape),
                   np.float32)
    adapters = {"attn": {"wq": {"a": jnp.asarray(a), "b": jnp.asarray(b)}}}
    merged = lora_merge(params, adapters, CFG, lcfg)
    want = (np.asarray(params["layers"]["attn"]["wq"])
            + 2.0 * np.einsum("ldr,lrhk->ldhk", a, b))
    np.testing.assert_allclose(np.asarray(merged["layers"]["attn"]["wq"]),
                               want, atol=1e-5, rtol=1e-5)
    # Untargeted weights are the same objects, not copies.
    assert merged["layers"]["attn"]["wk"] is params["layers"]["attn"]["wk"]


def test_validation():
    with pytest.raises(ValueError, match="unknown LoRA targets"):
        lora_init(jax.random.key(0), CFG, LoRAConfig(targets=("bogus",)))
    with pytest.raises(ValueError, match="rank"):
        lora_init(jax.random.key(0), CFG, LoRAConfig(rank=0))
    with pytest.raises(ValueError, match="nothing to train"):
        lora_init(jax.random.key(0), CFG, LoRAConfig(targets=()))
    with pytest.raises(ValueError, match="duplicate"):
        lora_init(jax.random.key(0), CFG,
                  LoRAConfig(targets=("wq", "wq")))
    moe = dataclasses.replace(CFG, num_experts=4)
    with pytest.raises(ValueError, match="dense-FFN"):
        lora_init(jax.random.key(0), moe, LoRAConfig(targets=("w1",)))
    lora_init(jax.random.key(0), moe, LoRAConfig(targets=("wq",)))  # ok


def test_finetune_trains_adapters_and_freezes_base(rng):
    base = toy_params(CFG)
    base_copy = jax.tree.map(lambda x: np.asarray(x).copy(), base)
    rows = _rows(rng)
    tr = dk.LoRATrainer(CFG, base, lora_rank=4, learning_rate=5e-2,
                        batch_size=16, num_epoch=4)
    merged = tr.train(rows)
    assert tr.history[-1] < tr.history[0], tr.history
    # The base never moved...
    flat = {"/".join(map(str, p)): v for p, v in
            jax.tree_util.tree_flatten_with_path(base_copy)[0]}
    # (recover the trained base from the packed state via the trainer's
    # adapters: merged - delta == base)
    re_merged = lora_merge(
        jax.tree.map(np.asarray, base_copy), tr.adapters, CFG, tr.lora)
    for k, v in {"/".join(map(str, p)): v for p, v in
                 jax.tree_util.tree_flatten_with_path(re_merged)[0]
                 }.items():
        np.testing.assert_allclose(
            np.asarray(v),
            np.asarray({"/".join(map(str, p)): q for p, q in
                        jax.tree_util.tree_flatten_with_path(merged)[0]}[k]),
            atol=1e-6, err_msg=k)
    del flat
    # ...and the adapters did.
    assert float(jnp.abs(tr.adapters["attn"]["wq"]["b"]).sum()) > 0


def test_optimizer_state_excludes_base(rng):
    """The LoRA memory win: masked optimizer moments exist for the
    adapter leaves only (no [V, D] / [L, D, F] moment buffers)."""
    base = toy_params(CFG)
    tr = dk.LoRATrainer(CFG, base, lora_rank=4, learning_rate=1e-2,
                        batch_size=16)
    packed = tr.init_params()
    state = tr.optimizer.init(packed)
    n_adapter = sum(x.size for x in jax.tree.leaves(packed[0]))
    n_base = sum(x.size for x in jax.tree.leaves(packed[1]))
    n_state = sum(x.size for x in jax.tree.leaves(state)
                  if hasattr(x, "size"))
    # adamw: two moments per ADAPTER element plus scalars — and nothing
    # proportional to the (much larger at real scale) base.
    assert n_state < 3 * n_adapter + 10, (n_state, n_adapter)
    assert n_base > 10 * n_adapter  # the toy config still separates scales


def test_merged_model_serves(rng):
    """The finetuned artifact drops into generate + quantize + save."""
    from helpers import generate
    from distkeras_tpu.models.quant import quantize_params

    base = toy_params(CFG)
    rows = _rows(rng, 32)
    tr = dk.LoRATrainer(CFG, base, lora_rank=2, learning_rate=1e-2,
                        batch_size=16, num_epoch=1)
    merged = tr.train(rows)
    prompt = jnp.asarray(rows[:2, :4])
    out = generate(merged, prompt, CFG, 6)
    assert out.shape == (2, 10)
    q = quantize_params(merged)
    qout = generate(q, prompt, CFG, 6)
    assert qout.shape == (2, 10)


def test_lora_composes_with_tp_mesh_and_segments(devices, rng):
    from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh

    cfg = dataclasses.replace(CFG, rope=True)
    base = toy_params(cfg)
    docs = [rng.integers(1, 64, (int(n),)).tolist()
            for n in rng.integers(5, 30, 40)]
    rows, segs = dk.pack_documents(docs, seq_len=16)
    n = (len(rows) // 8) * 8
    mesh = make_mesh(MeshSpec(data=4, model=2), devices=devices)
    tr = dk.LoRATrainer(cfg, base, lora_rank=4, learning_rate=3e-2,
                        batch_size=8, num_epoch=3, mesh=mesh,
                        eval_every=4)
    tr.train(rows[:n], segments=segs[:n],
             eval_tokens=rows[:8], eval_segments=segs[:8])
    assert tr.history[-1] < tr.history[0]
    assert all(np.isfinite(v["loss"]) for _, v in tr.eval_history)


def test_lora_checkpoint_resume_matches_straight(tmp_path, rng):
    base = toy_params(CFG)
    rows = _rows(rng)
    common = dict(lora_rank=4, learning_rate=1e-2, batch_size=16)
    d = str(tmp_path / "ck")
    straight = dk.LoRATrainer(CFG, base, num_epoch=2, **common)
    want = straight.train(rows)
    dk.LoRATrainer(CFG, base, num_epoch=1, checkpoint_dir=d,
                   **common).train(rows)
    resumed = dk.LoRATrainer(CFG, base, num_epoch=2, checkpoint_dir=d,
                             resume=True, **common)
    got = resumed.train(rows)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)
    assert len(resumed.history) == len(straight.history) // 2


def test_lora_mask_shape():
    lcfg = LoRAConfig(rank=2)
    adapters = lora_init(jax.random.key(0), CFG, lcfg)
    base = toy_params(CFG)
    mask = lora_mask((adapters, base))
    assert all(jax.tree.leaves(mask[0]))
    assert not any(jax.tree.leaves(mask[1]))


def test_train_rejects_params_argument(rng):
    base = toy_params(CFG)
    tr = dk.LoRATrainer(CFG, base, batch_size=16)
    with pytest.raises(ValueError, match="base_params"):
        tr.train(_rows(rng), params=base)
    with pytest.raises(ValueError, match="base_params"):
        dk.LoRATrainer(CFG, None)


def test_lora_merged_serves_speculatively(rng):
    """The full adapt-and-deploy composition: LoRA-finetuned merged
    tree serves via speculative decoding with its own int8 copy as the
    draft, matching generate's greedy rollout exactly."""
    from helpers import generate
    from distkeras_tpu.models.quant import quantize_params
    from distkeras_tpu.models.speculative import speculative_generate

    base = toy_params(CFG)
    rows = _rows(rng)
    tr = dk.LoRATrainer(CFG, base, lora_rank=4, learning_rate=3e-2,
                        batch_size=16, num_epoch=2)
    merged = tr.train(rows)
    draft = quantize_params(merged)
    prompt = jnp.asarray(rows[:4, :4])
    ref = np.asarray(generate(merged, prompt, CFG, 9))
    out, stats = speculative_generate(merged, draft, prompt, CFG, CFG,
                                      9, n_draft=3)
    np.testing.assert_array_equal(np.asarray(out), ref)
    assert float(stats["acceptance_rate"]) > 0.3  # int8 self-draft


def test_lora_grad_accum_matches_large_batch(rng):
    """grad_accum under the LoRA loss hook: accumulating microbatch
    gradients of the adapters equals one large-batch step (the
    masked-optimizer path composes with make_train_step's accum loop).
    """
    base = toy_params(CFG)
    rows = _rows(rng, 32)
    big = dk.LoRATrainer(CFG, base, lora_rank=4, learning_rate=1e-2,
                         batch_size=32, num_epoch=1)
    accum = dk.LoRATrainer(CFG, base, lora_rank=4, learning_rate=1e-2,
                           batch_size=16, grad_accum=2, num_epoch=1)
    want = big.train(rows)
    got = accum.train(rows)
    assert len(big.history) == len(accum.history) == 1
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)
