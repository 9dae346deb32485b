"""The file of the benchmark that calls into the system under test: the only
one, but for `published.py`, which holds a function that is the program's to
own until the program has it.

It has the program build its configuration from the published keys of the
configuration file, makes the weights on the device from the seed with the
builder the program gives that configuration, and starts `LLMServer` over a
`ContinuousBatcher` in-process through `jax_llama_tpu.run._serve_http`, the
same function `python -m jax_llama_tpu.run --http` ends in, so that every
server setting a cell does not name is `run.py`'s own default.
"""

from __future__ import annotations

import sys
import types
from typing import Any, Callable, Dict

# keys of a configuration file that are the benchmark's own, not the model's
_BOOKKEEPING = ("source", "architecture", "reference", "reduced", "assumed", "deployment")


def load_config(raw: Dict[str, Any], server: Dict[str, Any]):
    """The program's configuration of a configuration file (a dict of
    published `config.json` keys beside the file's bookkeeping) at a cell's
    `max_seq_len`.  The map is the program's `config.from_published` and,
    until it has one, the copy in `published.py`; both refuse a key they do
    not know."""
    from jax_llama_tpu import config as program

    build = getattr(program, "from_published", None)
    if build is None:
        from .published import from_published as build
    published = {k: v for k, v in raw.items() if k not in _BOOKKEEPING}
    try:
        config = build(published, max_seq_len=int(server["max_seq_len"]),
                       attn_impl=server.get("attn", "auto"))
    except ValueError as e:
        raise SystemExit(f"the configuration is refused: {e}")
    config.validate()
    return config


def build_mesh(server: Dict[str, Any], chips: int):
    """As `run.py` `main` builds it: `--serve-mesh` when the cell names one,
    else one tensor axis over the devices the cell asks for."""
    import jax

    devices = jax.devices()[:chips]
    if server.get("serve_mesh"):
        from jax_llama_tpu.parallel.serve_mesh import build_serve_mesh, parse_serve_mesh

        spec = parse_serve_mesh(server["serve_mesh"])
        if spec.n_devices != chips:
            raise SystemExit(f"serve_mesh {server['serve_mesh']} is not {chips} devices")
        return build_serve_mesh(spec, devices=devices)
    from jax_llama_tpu.parallel.mesh import make_mesh

    return make_mesh(data=1, fsdp=1, tensor=chips, devices=devices)


def make_params(config, mesh, seed: int):
    """Weights on the device in one jitted call from the seed, in the type
    they are served in, each leaf born in its shard."""
    import jax

    from jax_llama_tpu.models import init_params
    from jax_llama_tpu.parallel.partition import shard_abstract

    key = jax.random.PRNGKey(seed)
    shapes = jax.eval_shape(lambda: init_params(key, config))
    shardings = jax.tree_util.tree_map(
        lambda s: s.sharding, shard_abstract(shapes, mesh, config)
    )
    params = jax.jit(init_params, static_argnums=1, out_shardings=shardings)(key, config)
    return jax.block_until_ready(params)


class _NoTokenizer:
    """Requests carry token ids; nothing stops a generation but its
    `max_new_tokens`."""

    stop_tokens = ()
    eos_id = -1

    def decode(self, tokens) -> str:
        return ""

    def encode(self, *a, **kw):
        raise ValueError("this server takes token ids")


def serve(params, config, mesh, server: Dict[str, Any], seed: int,
          body: Callable[[Any], None]) -> None:
    """Run `body(srv)` against a live in-process `LLMServer`, then shut it
    down.  `server` holds the cell's settings under `run.py`'s option names;
    whatever it leaves out is `run.py`'s default (its `getattr` fallbacks)."""
    from jax_llama_tpu import run as program
    from jax_llama_tpu.obs import StructuredLogger

    args = types.SimpleNamespace(
        slots=int(server["slots"]), temperature=0.0, top_p=0.95, seed=seed % (2 ** 31),
        host="127.0.0.1", http=0, replicas=1,
        **{k: v for k, v in server.items()
           if k not in ("slots", "max_seq_len", "attn")},
    )
    if server.get("serve_mesh"):
        from jax_llama_tpu.parallel.serve_mesh import validate_serve_mesh

        validate_serve_mesh(config, mesh, args.slots)
    logger = StructuredLogger(json_mode=True, stream=sys.stderr)
    program._serve_http(
        params, config, _NoTokenizer(), mesh, args, _test_hook=body, logger=logger,
    )


def enable_compile_cache() -> str:
    """The program's own choice of cache directory (`<checkout>/.jax_cache`,
    or `JAX_COMPILATION_CACHE_DIR`), caching every program however small and
    evicting none: one cell's programs are some 200 MB (a `_fused_chunk`
    variant is 11 MB), and a size limit under that makes every run compile
    again what the last one evicted."""
    import jax

    from jax_llama_tpu.utils.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path
