"""Where the distance between the served latent stack (bf16) and its
float32 reference comes from, on the chip: 3,072 seeded tokens of
``joyai-llm-flash_l10-ep8``, the last 1,024 scored against
``benchmarks/reference_joyai.py`` — through the program's plain
``apply`` (expanded, no cache, no kernel), through admission chunks
(``mla_prefix_fwd``) and through decode steps (``mla_decode_fwd``);
then the reference against itself with four planted faults; then all
of it again with every feed-forward DENSE (no router).  One JSON line
a path: mean and worst gap to the reference's best logit (the cell's
two statistics), the share of choices that are the reference's best,
the mean and largest logit error.  TPU only, ~4 minutes.

    python scripts/diag_mla_distance.py
"""
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "benchmarks")]
import jax, jax.numpy as jnp, numpy as np
from distkeras_tpu.models import transformer as tfm, generate as gen
import reference_joyai as ref

conf = json.load(open(os.path.join(
    REPO, "benchmarks", "configs", "joyai-llm-flash_l10-ep8.json")))
T, KEEP, DEC = 3072, 2048, 128

def study(tag, tc):
    cfg = tfm.TransformerConfig(**tc)
    params = jax.jit(lambda k: jax.tree.map(lambda a: a.astype(jnp.bfloat16), tfm.init_params(k, cfg)))(jax.random.key(7))
    seq = np.random.default_rng(1).integers(0, cfg.vocab_size, T).astype(np.int32)
    normed = ref.forward(params, tc, seq, keep_from=KEEP, s_max=4096)
    want = ref.logits_at(params, normed, np.arange(T - KEEP))           # [1024, V]
    best = want.max(-1)
    def score(name, got, lo):                                              # got [n, V] for positions lo..lo+n
        w = want[lo - KEEP: lo - KEEP + len(got)]
        pick = np.asarray(got).argmax(-1)
        gap = w.max(-1) - w[np.arange(len(w)), pick]
        err = np.abs(np.asarray(got, np.float32) - w)
        print(json.dumps({"model": tag, "path": name, "n": len(w), "mean_gap": float(gap.mean()), "worst_gap": float(gap.max()),
                          "argmax_same": float((gap == 0).mean()), "mean_abs_logit_err": float(err.mean()), "max_abs_logit_err": float(err.max())}), flush=True)
    # (C) apply, expanded, no cache
    lg, _ = jax.jit(lambda p, t: tfm.apply(p, t, cfg))(params, jnp.asarray(seq)[None])
    score("apply_bf16", lg[0, KEEP:], KEEP)
    del lg
    # (A) chunks through the cache (mla_prefix_fwd), lane 1 of 2
    chunk = jax.jit(lambda p, c, rows, off: gen._decode_chunk(p, c, rows, off[None], cfg, uniform_pos=True, lane=jnp.int32(1)), donate_argnums=1)
    cache = gen.init_cache(cfg, 2)
    outs = []
    for lo in range(0, T - DEC, 512):
        lg, cache = chunk(params, cache, jnp.asarray(seq[None, lo:lo + 512]), jnp.int32(lo))
        if lo >= KEEP: outs.append(np.asarray(lg[0]))
    score("chunks", np.concatenate(outs), KEEP)
    # (B) decode steps (mla_decode_fwd) for the last DEC positions
    step = jax.jit(lambda p, c, cur, pos: gen._decode_chunk(p, c, cur[:, None], pos, cfg, live=jnp.asarray([0, 1])), donate_argnums=1)
    outs = []
    for t in range(T - DEC, T):
        lg, cache = step(params, cache, jnp.asarray([0, seq[t]], jnp.int32), jnp.asarray([cfg.max_len - 1, t], jnp.int32))
        outs.append(np.asarray(lg[1, 0]))
    score("decode", np.stack(outs), T - DEC)
    # the reference against itself with the latent in bf16 (what any cache of this dtype does), and in float8
    for fault in ("kv_float8", "matmul_float8", "top_k_less_one", "no_k_rope"):
        if "sparse" not in tc["ffn_types"] and fault == "top_k_less_one": continue
        n2 = ref.forward(params, tc, seq, keep_from=KEEP, s_max=4096, fault=fault)
        score("reference:" + fault, ref.logits_at(params, n2, np.arange(T - KEEP)), KEEP)

if jax.devices()[0].platform != "tpu":
    raise SystemExit("bf16 against float32 is the chip's to say")
tc = conf["transformer_config"]
study("joyai", tc)
dense = {**tc, "ffn_types": ["dense"] * 10, "num_experts": 0, "moe_top_k": 1, "moe_held": None, "moe_d_ff": None, "moe_shared": 0, "moe_route_scale": 1.0}
study("all_dense", dense)
