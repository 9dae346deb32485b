"""Converter + checkpoint tests: a synthetic 2-shard Meta-format checkpoint
(torch .pth, Megatron column/row splits) is converted and must reproduce the
oracle forward; Orbax roundtrip with and without mesh sharding."""

import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest
import torch

from jax_llama_tpu import config as cfg_lib
from jax_llama_tpu.convert import (
    convert_meta_checkpoint,
    load_checkpoint,
    save_checkpoint,
)
from jax_llama_tpu.models import forward, init_params
from jax_llama_tpu.parallel import make_mesh, use_mesh
import torch_oracle as oracle

# Synthetic model geometry (full, unsharded).
DIM, LAYERS, HEADS, KVH, VOCAB, MULT = 16, 2, 4, 2, 64, 16
HD = DIM // HEADS
CFG_KW = dict(dim=DIM, n_layers=LAYERS, n_heads=HEADS)


def _make_meta_ckpt(tmp_path, n_shards=2, with_output=True):
    """Build full random Meta-layout tensors, split them Megatron-style
    across shards, and torch.save each shard."""
    rng = np.random.RandomState(0)
    ffn = cfg_lib.swiglu_hidden_size(DIM, MULT)
    full = {"tok_embeddings.weight": rng.randn(VOCAB, DIM).astype(np.float32),
            "norm.weight": rng.randn(DIM).astype(np.float32)}
    if with_output:
        full["output.weight"] = rng.randn(VOCAB, DIM).astype(np.float32)
    for l in range(LAYERS):
        p = f"layers.{l}."
        full[p + "attention.wq.weight"] = rng.randn(HEADS * HD, DIM).astype(np.float32)
        full[p + "attention.wk.weight"] = rng.randn(KVH * HD, DIM).astype(np.float32)
        full[p + "attention.wv.weight"] = rng.randn(KVH * HD, DIM).astype(np.float32)
        full[p + "attention.wo.weight"] = rng.randn(DIM, HEADS * HD).astype(np.float32)
        full[p + "feed_forward.w1.weight"] = rng.randn(ffn, DIM).astype(np.float32)
        full[p + "feed_forward.w2.weight"] = rng.randn(DIM, ffn).astype(np.float32)
        full[p + "feed_forward.w3.weight"] = rng.randn(ffn, DIM).astype(np.float32)
        full[p + "attention_norm.weight"] = rng.randn(DIM).astype(np.float32)
        full[p + "ffn_norm.weight"] = rng.randn(DIM).astype(np.float32)

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
            else:  # norms replicated
                shard[key] = torch.from_numpy(arr.copy())
        torch.save(shard, tmp_path / f"consolidated.{s:02d}.pth")

    (tmp_path / "params.json").write_text(json.dumps({
        "dim": DIM, "n_layers": LAYERS, "n_heads": HEADS, "n_kv_heads": KVH,
        "multiple_of": MULT, "norm_eps": 1e-5, "rope_theta": 10000.0,
        "vocab_size": -1,
    }))
    return full


class _FakeTok:
    def __len__(self):
        return VOCAB


def test_convert_matches_oracle_forward(tmp_path):
    _make_meta_ckpt(tmp_path)
    params, config = convert_meta_checkpoint(
        tmp_path, _FakeTok(), max_seq_len=64, dtype="float32"
    )
    assert config.dim == DIM and config.n_layers == LAYERS
    assert config.kv_heads == KVH and config.vocab_size == VOCAB
    assert not config.tie_word_embeddings

    cfg = config.replace(dtype="float32")
    tokens = np.random.RandomState(1).randint(0, VOCAB, (2, 8))
    positions = np.tile(np.arange(8), (2, 1))
    got, _ = forward(params, jnp.asarray(tokens), jnp.asarray(positions), cfg)
    want = oracle.oracle_forward(params, tokens, positions, cfg)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=1e-4)


def test_convert_shard_reassembly_exact(tmp_path):
    full = _make_meta_ckpt(tmp_path, n_shards=2)
    params, config = convert_meta_checkpoint(
        tmp_path, vocab_size=VOCAB, dtype="float32"
    )
    # wq of layer 0: concat shards on axis 0, transpose, reshape to heads
    # — recovered from the fused qkv layout via split_qkv (which inverts
    # both the slot packing and the half-split RoPE feature permutation).
    from jax_llama_tpu.models import split_qkv

    got_q, got_k, got_v = split_qkv(np.asarray(params["layers"]["qkv"][0]))
    want_q = full["layers.0.attention.wq.weight"].T.reshape(DIM, HEADS, HD)
    np.testing.assert_array_equal(got_q, want_q)
    want_k = full["layers.0.attention.wk.weight"].T.reshape(DIM, KVH, HD)
    np.testing.assert_array_equal(got_k, want_k)
    want_v = full["layers.0.attention.wv.weight"].T.reshape(DIM, KVH, HD)
    np.testing.assert_array_equal(got_v, want_v)
    want_up = full["layers.0.feed_forward.w3.weight"].T
    np.testing.assert_array_equal(
        params["layers"]["gate_up"][0][1], want_up
    )
    want_o = full["layers.0.attention.wo.weight"].T.reshape(HEADS, HD, DIM)
    np.testing.assert_array_equal(params["layers"]["o"][0], want_o)
    np.testing.assert_array_equal(
        params["embed"]["embedding"], full["tok_embeddings.weight"]
    )
    np.testing.assert_array_equal(
        params["lm_head"], full["output.weight"].T
    )


def test_convert_vocab_parallel_embedding(tmp_path):
    """Llama-3 layout: tok_embeddings split on the vocab axis."""
    full = _make_meta_ckpt(tmp_path, n_shards=2)
    # Rewrite shards with the embedding split on axis 0 instead of axis 1.
    for s in range(2):
        p = tmp_path / f"consolidated.{s:02d}.pth"
        sd = torch.load(p, weights_only=True)
        sd["tok_embeddings.weight"] = torch.from_numpy(
            np.split(full["tok_embeddings.weight"], 2, axis=0)[s].copy()
        )
        torch.save(sd, p)
    params, _ = convert_meta_checkpoint(
        tmp_path, vocab_size=VOCAB, dtype="float32"
    )
    np.testing.assert_array_equal(
        params["embed"]["embedding"], full["tok_embeddings.weight"]
    )


def test_convert_rejects_unknown_arch_keys(tmp_path):
    _make_meta_ckpt(tmp_path)
    pj = json.loads((tmp_path / "params.json").read_text())
    pj["quantization_scheme"] = "fp8"
    (tmp_path / "params.json").write_text(json.dumps(pj))
    with pytest.raises(ValueError, match="quantization_scheme"):
        convert_meta_checkpoint(tmp_path, vocab_size=VOCAB)


def test_convert_consumes_use_scaled_rope(tmp_path):
    _make_meta_ckpt(tmp_path)
    pj = json.loads((tmp_path / "params.json").read_text())
    pj["use_scaled_rope"] = True
    (tmp_path / "params.json").write_text(json.dumps(pj))
    _, config = convert_meta_checkpoint(tmp_path, vocab_size=VOCAB)
    assert config.use_scaled_rope


def test_convert_fp32_keeps_fp32_compute(tmp_path):
    _make_meta_ckpt(tmp_path)
    _, config = convert_meta_checkpoint(
        tmp_path, vocab_size=VOCAB, dtype="float32"
    )
    assert config.dtype == "float32" and config.param_dtype == "float32"


def test_convert_single_shard_and_tied(tmp_path):
    _make_meta_ckpt(tmp_path, n_shards=1, with_output=False)
    params, config = convert_meta_checkpoint(
        tmp_path, vocab_size=VOCAB, dtype="float32"
    )
    assert config.tie_word_embeddings
    assert "lm_head" not in params


def test_convert_bf16_dtype(tmp_path):
    _make_meta_ckpt(tmp_path)
    params, _ = convert_meta_checkpoint(tmp_path, vocab_size=VOCAB)
    assert params["layers"]["qkv"].dtype == jnp.bfloat16
    assert params["embed"]["embedding"].dtype == jnp.bfloat16


def test_orbax_roundtrip(tmp_path):
    cfg = cfg_lib.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ckpt = tmp_path / "ckpt"
    save_checkpoint(ckpt, params, cfg)
    restored, rcfg = load_checkpoint(ckpt)
    assert rcfg == cfg
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params, restored,
    )


def test_orbax_old_layout_checkpoint_migrates(tmp_path):
    """Rounds 1-2 checkpoints stored separate q/k/v + gate/up (Meta
    interleaved RoPE feature order): load_checkpoint must detect the old
    tree, restore it, and fuse_params-migrate — same forward after."""
    import orbax.checkpoint as ocp

    from jax_llama_tpu.models import split_qkv

    cfg = cfg_lib.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    # Construct the old on-disk layout from the fused tree (split_qkv
    # inverts both the packing and the rope permutation — exactly what an
    # old checkpoint held).
    lp = dict(params["layers"])
    q, k, v = split_qkv(lp.pop("qkv"))
    gate_up = lp.pop("gate_up")
    lp.update(q=q, k=k, v=v, gate=gate_up[:, 0], up=gate_up[:, 1])
    old = dict(params)
    old["layers"] = lp

    import dataclasses as _dc
    import json as _json

    ckpt = tmp_path / "old_ckpt"
    ckpt.mkdir()
    (ckpt / "config.json").write_text(
        _json.dumps(dict(_dc.asdict(cfg), _quantized=False))
    )
    ckptr = ocp.StandardCheckpointer()
    ckptr.save((ckpt / "params").absolute(), old, force=True)
    ckptr.wait_until_finished()

    restored, rcfg = load_checkpoint(ckpt)
    assert rcfg == cfg
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        restored, params,
    )


def test_orbax_sharded_restore(tmp_path):
    cfg = cfg_lib.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ckpt = tmp_path / "ckpt"
    save_checkpoint(ckpt, params, cfg)
    mesh = make_mesh(tensor=2, data=4)
    restored, rcfg = load_checkpoint(ckpt, mesh=mesh)
    qkv = restored["layers"]["qkv"]
    shard_shapes = {s.data.shape for s in qkv.addressable_shards}
    G = cfg.n_heads // cfg.kv_heads
    assert shard_shapes == {
        (cfg.n_layers, cfg.kv_heads // 2, G + 2, cfg.dim, cfg.head_dim)
    }
    # Restored-sharded forward == original.
    tokens = jnp.asarray([[1, 2, 3, 4]])
    pos = jnp.arange(4)[None, :]
    with use_mesh(mesh):
        got = np.asarray(jax.jit(
            lambda p, t, q_: forward(p, t, q_, cfg)[0])(restored, tokens, pos))
    want, _ = forward(params, tokens, pos, cfg)
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-5)


def test_train_state_resume_roundtrip(tmp_path):
    """Train 2 steps -> save -> restore (sharded) -> the next step must be
    bit-identical to training straight through (optimizer moments intact)."""
    import numpy as np
    from jax_llama_tpu import get_config, init_params, make_mesh
    from jax_llama_tpu.convert.checkpoint import (
        load_train_state,
        save_train_state,
    )
    from jax_llama_tpu.parallel import shard_params
    from jax_llama_tpu.train import (
        init_train_state,
        make_optimizer,
        train_step,
    )

    config = get_config(
        "tiny", vocab_size=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        multiple_of=32, max_seq_len=16,
    )
    mesh = make_mesh(data=2, tensor=2, devices=jax.devices()[:4])
    opt = make_optimizer(1e-3)
    params = shard_params(init_params(jax.random.PRNGKey(0), config), mesh, config)
    state = init_train_state(params, opt)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (4, 16)), jnp.int32
    )
    for _ in range(2):
        state, _ = train_step(state, tokens, config, opt, mesh=mesh)

    save_train_state(str(tmp_path / "tstate"), state, config)
    restored, rconfig = load_train_state(
        str(tmp_path / "tstate"), opt, mesh=mesh
    )
    assert rconfig == config
    assert int(restored.step) == 2
    # continue training from both and compare exactly
    cont_a, loss_a = train_step(state, tokens, config, opt, mesh=mesh)
    cont_b, loss_b = train_step(restored, tokens, config, opt, mesh=mesh)
    assert float(loss_a) == float(loss_b)
    for a, b in zip(jax.tree.leaves(cont_a.params), jax.tree.leaves(cont_b.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_kind_mismatch_errors():
    """Pointing the wrong loader at a checkpoint gives a clear error, not a
    TypeError from config parsing."""
    import pytest
    from jax_llama_tpu import get_config, init_params
    from jax_llama_tpu.convert.checkpoint import (
        load_checkpoint,
        load_train_state,
        save_checkpoint,
        save_train_state,
    )
    from jax_llama_tpu.train import init_train_state, make_optimizer

    config = get_config(
        "tiny", vocab_size=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        multiple_of=32, max_seq_len=16,
    )
    params = init_params(jax.random.PRNGKey(0), config)
    opt = make_optimizer(1e-3)
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        save_train_state(td + "/t", init_train_state(params, opt), config)
        with pytest.raises(ValueError, match="training checkpoint"):
            load_checkpoint(td + "/t")
        save_checkpoint(td + "/s", params, config)
        with pytest.raises(ValueError, match="serving checkpoint"):
            load_train_state(td + "/s", opt)


def test_orbax_d_first_layout_checkpoint_migrates(tmp_path):
    """r3 checkpoints stored the fused weights with the contracted D axis
    leading; load_checkpoint must detect the layout from metadata and
    migrate by axis permutation — exact for full-precision AND int8 trees
    (payload and scale permute together)."""
    import dataclasses as _dc
    import json as _json

    import orbax.checkpoint as ocp

    from jax_llama_tpu.convert.checkpoint import _to_d_first
    from jax_llama_tpu.ops.quant import QuantizedTensor, quantize_params

    cfg = cfg_lib.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)

    def save_as_d_first(tree, path, quantized):
        old = dict(tree)
        old["layers"] = _to_d_first(tree["layers"])
        path.mkdir()
        (path / "config.json").write_text(
            _json.dumps(dict(_dc.asdict(cfg), _quantized=quantized))
        )
        ckptr = ocp.StandardCheckpointer()
        ckptr.save((path / "params").absolute(), old, force=True)
        ckptr.wait_until_finished()

    save_as_d_first(params, tmp_path / "fp", quantized=False)
    restored, rcfg = load_checkpoint(tmp_path / "fp")
    assert rcfg == cfg
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        restored, params,
    )

    qp = quantize_params(params)
    save_as_d_first(qp, tmp_path / "q8", quantized=True)
    restored_q, _ = load_checkpoint(tmp_path / "q8")
    assert isinstance(restored_q["layers"]["qkv"], QuantizedTensor)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        restored_q, qp,
    )


def test_checkpoint_manifest_rejects_corrupt_shard(tmp_path):
    """The save-time sha256 manifest makes a flipped byte (or a
    truncated file) in any shard fail the restore loudly BEFORE serving
    starts — never silent garbage weights."""
    from jax_llama_tpu.convert.checkpoint import (
        MANIFEST_NAME,
        verify_manifest,
    )

    cfg = cfg_lib.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ckpt = tmp_path / "ckpt"
    save_checkpoint(ckpt, params, cfg)
    manifest = json.loads((ckpt / MANIFEST_NAME).read_text())
    assert manifest["files"]  # every file hashed at save time
    assert verify_manifest(ckpt) is True

    # Flip one byte in the LARGEST shard (an actual array payload).
    rel = max(manifest["files"], key=lambda r: manifest["files"][r]["bytes"])
    shard = ckpt / rel
    blob = bytearray(shard.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    shard.write_bytes(bytes(blob))
    with pytest.raises(ValueError, match="sha256 mismatch"):
        load_checkpoint(ckpt)
    # verify=False opts out (storage-layer-integrity escape hatch).
    load_checkpoint(ckpt, verify=False)

    # Truncation is reported as truncation, checked before hashing.
    shard.write_bytes(bytes(blob[: len(blob) // 2]))
    with pytest.raises(ValueError, match="truncated"):
        load_checkpoint(ckpt)

    # A deleted shard is reported missing.
    shard.unlink()
    with pytest.raises(ValueError, match="missing"):
        load_checkpoint(ckpt)


def test_checkpoint_atomic_overwrite_keeps_manifest_consistent(tmp_path):
    """Re-saving over an existing checkpoint swaps the whole tree: the
    manifest always describes exactly the files on disk (no stale trash
    or temp siblings left behind)."""
    import os

    cfg = cfg_lib.tiny()
    ckpt = tmp_path / "ckpt"
    save_checkpoint(ckpt, init_params(jax.random.PRNGKey(0), cfg), cfg)
    save_checkpoint(ckpt, init_params(jax.random.PRNGKey(1), cfg), cfg)
    restored, _ = load_checkpoint(ckpt)
    want = init_params(jax.random.PRNGKey(1), cfg)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        want, restored,
    )
    # No .tmp-/.trash- siblings survive a completed save.
    leftovers = [n for n in os.listdir(tmp_path)
                 if ".tmp-" in n or ".trash-" in n]
    assert leftovers == []


def test_checkpoint_with_retired_config_keys_loads(tmp_path):
    """Every checkpoint saved before PR 30 carries `prefill_kernel` /
    `decode_kernel` in its config.json (fields of the config then): the
    loader drops exactly those two, and any other unknown key still
    fails."""
    from jax_llama_tpu.convert.checkpoint import _write_manifest

    cfg = cfg_lib.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ckpt = tmp_path / "ckpt"
    save_checkpoint(ckpt, params, cfg)

    def rewrite(**extra):
        meta = json.loads((ckpt / "config.json").read_text())
        meta.update(extra)
        (ckpt / "config.json").write_text(json.dumps(meta))
        _write_manifest(ckpt)

    assert "prefill_kernel" not in json.loads((ckpt / "config.json").read_text())
    rewrite(prefill_kernel="flash", decode_kernel="paged")
    restored, rcfg = load_checkpoint(ckpt)
    assert rcfg == cfg
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params, restored,
    )
    rewrite(flash_kernel="on")
    with pytest.raises(TypeError, match="flash_kernel"):
        load_checkpoint(ckpt)
