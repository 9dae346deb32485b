"""On the chip: `mla_moe.attend_tiled` alone at kanana-2-30b-a3b's widths (32
heads, r 512, 128 nope + 64 rope, values 128, cache row 640; Xing4.0-29B-A4B's
are the same) — a prompt chunk of 2,048 tokens, and one of 512, behind 0, 1, 3, 4
and 7 live tiles of a 16,384-slot view of 8 layers.  The measurement behind
PERF.md section 6, PR 54: ms a call on the host's clock over 20 calls, the time
a live tile adds ((7 tiles - 1 tile) / 6), the chunk over itself alone, and the
error against the one-piece XLA form with a float32 softmax.

    chiprun -- python3 tests/tools/latent_tile.py <label>
    JAX_PLATFORMS=cpu VIEW=128 TS=16 python tests/tools/latent_tile.py small   # here: the flow, interpreted, tiny

The signature of `attend_tiled` is the one PR 29 gave it, so the same file runs
from the root of an older tree (`cd <tree> && python3 <this file> parent`) for a
pair.  Not a test: tier-1 does not collect it."""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp, numpy as np
from jax_llama_tpu import config as config_mod
from jax_llama_tpu.models import mla_moe
from jax_llama_tpu.models.llama import KVCache
from jax_llama_tpu.ops.attention import attention_bias

label = sys.argv[1]
cfg = config_mod.LLaMAConfig(n_heads=32, kv_lora_rank=512, qk_nope_head_dim=128,
                             qk_rope_head_dim=64, v_head_dim=128, dtype="bfloat16")
L, view, w, H = 8, int(os.environ.get("VIEW", 16384)), cfg.cache_width, 32
TILE = view // 8
mla_moe.CTX_TILE = TILE
TS = tuple(int(t) for t in os.environ.get("TS", "2048,512").split(","))
bf16, i32 = jnp.bfloat16, jnp.int32
key = jax.random.PRNGKey(0)
ks = jax.random.split(key, 8)
cache_k = (jax.random.normal(ks[0], (L, 1, view, 1, w), jnp.float32) * 0.5).astype(bf16)
kv_b = (jax.random.normal(ks[1], (H, 512, 256), jnp.float32) * 0.05).astype(bf16)


def operands(T, k):
    q_nope = jax.random.normal(jax.random.fold_in(k, 0), (1, T, H, 128), jnp.float32).astype(bf16)
    q_rope = jax.random.normal(jax.random.fold_in(k, 1), (1, T, H, 64), jnp.float32).astype(bf16)
    latent = (jax.random.normal(jax.random.fold_in(k, 2), (1, T, w), jnp.float32) * 0.5).astype(bf16)
    return q_nope, q_rope, latent


@jax.jit
def attend(q_nope, q_rope, latent, kv_b, k, index, layer):
    T = q_nope.shape[1]
    pos = jnp.where(jnp.arange(view)[None] < index, jnp.arange(view)[None], -1).astype(i32)
    q_pos = (index + jnp.arange(T, dtype=i32))[None]
    cache = KVCache(k=k, v=None, pos=pos, index=index)
    return mla_moe.attend_tiled(q_nope, q_rope, latent, kv_b, q_pos, q_pos, cache, layer, cfg)


def reference(q_nope, q_rope, latent, index, layer):
    """One piece, plain XLA, float32 softmax over the live slots + the chunk."""
    T = q_nope.shape[1]
    seen = jnp.concatenate([cache_k[layer, :, :index, 0], latent], axis=1)
    kv_pos = jnp.arange(index + T, dtype=i32)[None]
    q_pos = (index + jnp.arange(T, dtype=i32))[None]
    bias = attention_bias(q_pos, kv_pos, kv_pos >= 0)
    c32 = cfg.replace(attn_softmax_dtype="float32")
    return mla_moe.attend_decompressed(q_nope, q_rope, seen, kv_b, q_pos, kv_pos, bias, c32, False)


out = {"label": label, "device": str(jax.devices()[0].device_kind), "rows": []}
for T in TS:
    ops = operands(T, ks[2])
    for index in (0, TILE, 3 * TILE, 4 * TILE, 7 * TILE):
        args = (*ops, kv_b, cache_k, jnp.int32(index), jnp.int32(3))
        y = attend(*args).block_until_ready()
        t0 = time.perf_counter()
        n = 20
        for _ in range(n):
            y = attend(*args)
        y.block_until_ready()
        ms = (time.perf_counter() - t0) / n * 1e3
        row = {"T": T, "index": index, "tiles": -(-index // TILE), "ms": round(ms, 4)}
        if index in (0, 3 * TILE):
            ref = jax.jit(reference, static_argnums=(3, 4))(*ops, index, 3)
            d = np.abs(np.asarray(y, np.float32) - np.asarray(ref, np.float32))
            row["max_abs_err"] = float(d.max())
            row["mean_abs_err"] = float(d.mean())
            row["ref_abs_mean"] = float(np.abs(np.asarray(ref, np.float32)).mean())
        out["rows"].append(row)
        print(json.dumps(row), flush=True)
for T in TS:
    r = {x["index"]: x["ms"] for x in out["rows"] if x["T"] == T}
    out[f"ms_per_tile_T{T}"] = round((r[7 * TILE] - r[TILE]) / 6, 4)
    out[f"ms_self_T{T}"] = r[0]
print(json.dumps(out))
