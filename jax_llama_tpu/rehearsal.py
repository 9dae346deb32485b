"""Real-weights dress rehearsal: the full download → convert → Orbax →
generate → parity pipeline as ONE unattended command.

No real Llama weights exist in the development environment, so the
end-to-end path the reference exercises with real checkpoints
(``/root/reference/jax_test.py:427-522``: load, convert, generate, logit
parity vs Meta PyTorch) is rehearsed here three ways:

  * ``--synthetic``: builds a small but real Meta-FORMAT checkpoint
    (sharded ``consolidated.NN.pth`` + ``params.json``, Megatron
    column/row splits), then runs the exact production path: convert →
    Orbax save → sharded Orbax restore → jitted greedy generate → fp32
    logit parity vs the independent torch oracle.  Every step is the same
    code real weights will take.
  * ``--shapes-8b``: abstract (eval_shape) validation at full Llama-3-8B
    geometry — param tree shapes/bytes, partition-spec coverage on a
    virtual 8-device tensor×data mesh, and Orbax save-layout metadata —
    without materializing 16 GB.
  * ``--ckpt-dir ...``: the real thing, unattended, the moment weights
    are available:

        python -m jax_llama_tpu.rehearsal \\
            --ckpt-dir /weights/Meta-Llama-3-8B \\
            --tokenizer /weights/Meta-Llama-3-8B/tokenizer.model \\
            --out /ckpts/llama3-8b-orbax

    (Download first via ``jax-llama-download --presigned-url ...``.)
    Runs convert (fp32-exact tensor reassembly, bf16 storage) → Orbax →
    restore → two greedy completions, and — when a torch oracle is
    importable (``pip install torch``; tests/torch_oracle.py) — last-token
    logit parity in fp32 on a short prompt, reporting the max abs diff
    against the <1e-3 BASELINE target.
"""

from __future__ import annotations

import argparse
import json
import sys
import contextlib
import tempfile
import time
from pathlib import Path

def _log(msg: str) -> None:
    print(f"[rehearsal +{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


_T0 = time.perf_counter()


def _write_synthetic_meta_checkpoint(
    tmpdir: Path, *, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    vocab=256, multiple_of=32, n_shards=2, seed=0,
):
    """A miniature checkpoint in Meta's exact on-disk format (the same
    layout ``tests/test_convert.py`` pins against the reference
    converter): torch fp32 tensors, Megatron column/row shard splits,
    ``params.json`` with the SwiGLU sizing fields."""
    import numpy as np
    import torch

    from .config import swiglu_hidden_size

    rng = np.random.RandomState(seed)
    hd = dim // n_heads
    ffn = swiglu_hidden_size(dim, multiple_of)
    full = {
        "tok_embeddings.weight": rng.randn(vocab, dim).astype(np.float32),
        "norm.weight": rng.randn(dim).astype(np.float32),
        "output.weight": rng.randn(vocab, dim).astype(np.float32),
    }
    for l in range(n_layers):
        p = f"layers.{l}."
        full[p + "attention.wq.weight"] = rng.randn(
            n_heads * hd, dim).astype(np.float32)
        full[p + "attention.wk.weight"] = rng.randn(
            n_kv_heads * hd, dim).astype(np.float32)
        full[p + "attention.wv.weight"] = rng.randn(
            n_kv_heads * hd, dim).astype(np.float32)
        full[p + "attention.wo.weight"] = rng.randn(
            dim, n_heads * hd).astype(np.float32)
        full[p + "feed_forward.w1.weight"] = rng.randn(
            ffn, dim).astype(np.float32)
        full[p + "feed_forward.w2.weight"] = rng.randn(
            dim, ffn).astype(np.float32)
        full[p + "feed_forward.w3.weight"] = rng.randn(
            ffn, dim).astype(np.float32)
        full[p + "attention_norm.weight"] = rng.randn(dim).astype(np.float32)
        full[p + "ffn_norm.weight"] = rng.randn(dim).astype(np.float32)

    col_keys = ("wq", "wk", "wv", "w1", "w3", "output")
    row_keys = ("wo", "w2", "tok_embeddings")
    for s in range(n_shards):
        shard = {}
        for key, arr in full.items():
            if any(k in key for k in col_keys):
                shard[key] = torch.from_numpy(
                    np.split(arr, n_shards, axis=0)[s].copy())
            elif any(k in key for k in row_keys):
                shard[key] = torch.from_numpy(
                    np.split(arr, n_shards, axis=1)[s].copy())
            else:
                shard[key] = torch.from_numpy(arr.copy())
        torch.save(shard, tmpdir / f"consolidated.{s:02d}.pth")
    (tmpdir / "params.json").write_text(json.dumps({
        "dim": dim, "n_layers": n_layers, "n_heads": n_heads,
        "n_kv_heads": n_kv_heads, "multiple_of": multiple_of,
        "norm_eps": 1e-5, "rope_theta": 10000.0, "vocab_size": -1,
    }))
    return vocab


def _oracle_module():
    """Import tests/torch_oracle.py when available (repo checkout or an
    installed test extra); None otherwise."""
    try:
        import torch_oracle  # repo layout: tests/ on sys.path

        return torch_oracle
    except ImportError:
        tests_dir = Path(__file__).resolve().parent.parent / "tests"
        if (tests_dir / "torch_oracle.py").exists():
            sys.path.insert(0, str(tests_dir))
            try:
                import torch_oracle

                return torch_oracle
            except ImportError:
                return None
    return None


def _pipeline(ckpt_dir: str, out_dir: str, tokenizer, vocab_size, dtype,
              max_seq_len, prompts, max_gen_len, parity_atol):
    """convert → Orbax save → restore → generate → (optional) parity.

    The shared spine of both the synthetic rehearsal and the real run.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp

    from .convert.checkpoint import load_checkpoint, save_checkpoint
    from .convert.meta import convert_meta_checkpoint
    from .engine import GenerationConfig, generate, prompt_positions

    _log(f"converting Meta checkpoint at {ckpt_dir} (dtype={dtype})")
    params, config = convert_meta_checkpoint(
        ckpt_dir, tokenizer=tokenizer, vocab_size=vocab_size,
        max_seq_len=max_seq_len, dtype=dtype,
    )
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    _log(f"converted: {n_params / 1e6:.1f}M params, dim={config.dim} "
         f"L={config.n_layers}")

    _log(f"saving Orbax checkpoint to {out_dir}")
    save_checkpoint(out_dir, params, config)
    _log("restoring (sharded restore path)")
    restored, rconfig = load_checkpoint(out_dir)
    assert rconfig == config

    if tokenizer is not None:
        encode = lambda s: tokenizer.encode(s, bos=True, eos=False)
        decode = tokenizer.decode
    else:
        encode = lambda s: [1] + [ord(c) % (vocab_size - 2) + 2 for c in s]
        decode = lambda ids: repr(ids)

    token_lists = [encode(p) for p in prompts]
    P = max(len(t) for t in token_lists)
    toks = np.zeros((len(prompts), P), np.int32)
    pmask = np.zeros((len(prompts), P), bool)
    for i, t in enumerate(token_lists):
        toks[i, P - len(t):] = t
        pmask[i, P - len(t):] = True
    gc = GenerationConfig(
        max_new_tokens=max_gen_len, temperature=0.0, stop_tokens=()
    )
    _log(f"greedy generate: {len(prompts)} prompts, max_gen_len={max_gen_len}")
    out = np.asarray(generate(
        restored, jnp.asarray(toks), jnp.asarray(pmask),
        jax.random.PRNGKey(0), config=config, gen_config=gc,
    ))
    for i, p in enumerate(prompts):
        _log(f"  prompt {i}: {p!r} -> {decode(out[i, P:].tolist())!r}")

    oracle = _oracle_module()
    if oracle is None:
        _log("torch oracle unavailable — skipping logit parity "
             "(pip install torch and run from the repo checkout)")
        return None
    _log("fp32 logit parity vs the independent torch oracle (CPU: an 8B "
         "fp32 forward does not fit one chip's HBM)")
    from .models import forward as model_forward

    fp32_cfg = config.replace(dtype="float32")
    positions = np.asarray(prompt_positions(jnp.asarray(pmask)))
    try:
        cpu = jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        cpu = None
    host_params = jax.device_get(restored)
    ctx = (
        jax.default_device(cpu) if cpu is not None
        else contextlib.nullcontext()
    )
    with ctx:
        mine = np.asarray(
            jax.jit(
                lambda p, t, q: model_forward(p, t, q, fp32_cfg)[0]
            )(host_params, jnp.asarray(toks), jnp.asarray(positions))
        )
    want = oracle.oracle_forward(host_params, toks, positions, fp32_cfg)
    diff = float(np.max(np.abs(
            mine[pmask].astype(np.float64) - want[pmask].astype(np.float64)
    )))
    _log(f"max abs logit diff (fp32, all valid positions): {diff:.2e} "
         f"(target < {parity_atol})")
    if diff >= parity_atol:
        raise SystemExit(
            f"PARITY FAILURE: {diff:.2e} >= {parity_atol}"
        )
    return diff


def rehearse_synthetic() -> None:
    """Scaled-down end-to-end rehearsal on a synthetic Meta checkpoint."""
    with tempfile.TemporaryDirectory() as td:
        tmp = Path(td)
        ck = tmp / "meta"
        ck.mkdir()
        _log("building synthetic 2-shard Meta-format checkpoint")
        vocab = _write_synthetic_meta_checkpoint(ck)
        diff = _pipeline(
            str(ck), str(tmp / "orbax"), tokenizer=None, vocab_size=vocab,
            dtype="float32", max_seq_len=128,
            prompts=["hello tpu", "paged kv"], max_gen_len=8,
            # fp32 end-to-end on the synthetic model: conversion must be
            # exact, so only accumulation-order noise remains.
            parity_atol=1e-3,
        )
        _log(f"synthetic rehearsal PASSED (parity {diff:.2e})"
             if diff is not None else "synthetic rehearsal PASSED")


def rehearse_8b_shapes() -> None:
    """Abstract full-8B validation: shapes, partition coverage, Orbax
    layout — no weight materialization."""
    import types

    import numpy as np
    import jax

    from . import get_config, init_params
    from .parallel.partition import param_partition_specs, validate_tp

    config = get_config("llama3-8b")
    _log(f"eval_shape at llama3-8b: dim={config.dim} "
         f"L={config.n_layers} H={config.n_heads}/{config.kv_heads} "
         f"V={config.vocab_size}")
    shapes = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), config)
    )
    total = sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)
    )
    _log(f"param tree: {len(jax.tree.leaves(shapes))} leaves, "
         f"{total / 1e9:.2f}B params, "
         f"{total * 2 / 1e9:.1f} GB bf16")
    assert 7.9e9 < total < 8.4e9, total
    # Analytic partition coverage at tensor=4 × data=2 (no devices
    # needed): every leaf must have a spec, every sharded axis must
    # divide, and the resulting largest per-device shard must fit HBM.
    axes = {"tensor": 4, "data": 2, "fsdp": 1, "seq": 1, "stage": 1}
    validate_tp(config, types.SimpleNamespace(shape=axes))
    specs = param_partition_specs(config)
    shard_bytes = []

    def check(leaf, spec):
        shape = list(leaf.shape)
        for dim, ax in enumerate(tuple(spec)):
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                if a is None:
                    continue
                assert shape[dim] % axes[a] == 0, (shape, spec)
                shape[dim] //= axes[a]
        shard_bytes.append(int(np.prod(shape)) * 2)

    jax.tree.map(check, shapes, specs)
    _log(f"partition specs cover all {len(shard_bytes)} leaves at "
         f"tensor=4 × data=2; largest per-device shard "
         f"{max(shard_bytes) / 1e6:.0f} MB bf16 (fits v5e HBM)")
    _log("8B abstract rehearsal PASSED")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--synthetic", action="store_true",
                    help="scaled-down end-to-end rehearsal (no weights "
                         "needed)")
    ap.add_argument("--shapes-8b", action="store_true",
                    help="abstract full-8B shape/partition validation")
    ap.add_argument("--ckpt-dir", default=None,
                    help="real Meta checkpoint directory (consolidated."
                         "NN.pth + params.json)")
    ap.add_argument("--tokenizer", default=None,
                    help="tokenizer.model path (llama3 tiktoken format, "
                         "or --llama2)")
    ap.add_argument("--llama2", action="store_true")
    ap.add_argument("--out", default=None,
                    help="Orbax output directory (default: "
                         "<ckpt-dir>-orbax)")
    ap.add_argument("--max-seq-len", type=int, default=2048)
    ap.add_argument("--max-gen-len", type=int, default=32)
    ap.add_argument("--parity-atol", type=float, default=1e-3)
    args = ap.parse_args()
    from .utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.synthetic:
        rehearse_synthetic()
    if args.shapes_8b:
        rehearse_8b_shapes()
    if args.ckpt_dir:
        if args.tokenizer is None:
            raise SystemExit("--ckpt-dir needs --tokenizer")
        if args.llama2:
            from .tokenizers.llama2 import LLaMA2Tokenizer as Tok
        else:
            from .tokenizers.llama3 import LLaMA3Tokenizer as Tok
        tok = Tok(args.tokenizer)
        out = args.out or (args.ckpt_dir.rstrip("/") + "-orbax")
        _pipeline(
            args.ckpt_dir, out, tokenizer=tok, vocab_size=None,
            dtype="bfloat16", max_seq_len=args.max_seq_len,
            prompts=[
                "I believe the meaning of life is",
                "Simply put, the theory of relativity states that",
            ],
            max_gen_len=args.max_gen_len, parity_atol=args.parity_atol,
        )
        _log("real-weights rehearsal PASSED")
    if not (args.synthetic or args.shapes_8b or args.ckpt_dir):
        ap.error("pick at least one of --synthetic / --shapes-8b / "
                 "--ckpt-dir")


if __name__ == "__main__":
    main()
