"""On the chip: upstream `megablox.gmm` alone, by tile, at every distinct
grouped product of the four configurations with routed experts: gate/up
`[D, 2F]` and down `[F, D]`, for a decode iteration's one row tile, a
512-token chunk and a 2048-token chunk.  The sweep that chose
`ops/moe._tiling` (PERF.md section 6, PR 52).

    chiprun -- python3 tests/tools/gmm_tiles.py [configuration ...]
    COMPILE_ONLY=1 python tests/tools/gmm_tiles.py   # here: what a described v5e compiles
    SMALL=1 python tests/tools/gmm_tiles.py          # here: the flow, interpreted, tiny

Group sizes are UNEVEN and UNALIGNED: a multinomial over the experts whose
probabilities are log-normal, their width found by bisection so that the
largest probability is the cell's `moe.max_load_share_pct` (the ledger's),
and all L * E experts are handed to the kernel with zeros outside one layer,
as `grouped_matmul` does.  A decode iteration draws slots * top_k pairs into
one 128-row tile.  Every line gives the draw's largest share, the experts it
touched, the kernel's visits ((row tile, expert) pairs: row tiles plus the
groups that start inside one) and, for a tile, `ms` — the host's clock over
30 whole calls, the group metadata's small operations included — and
`kernel_ms` — the Mosaic call alone, the median of its device events in a
profiler trace, which is what a cell's `_gmm.*` lines read — with the touched
experts' weight bytes and the pairs' operations over the kernel's time.
`ladder` is the rule before PR 52, `rule` what `_tiling` returns now.  Not a
test: tier-1 does not collect it."""
import glob
import json
import os
import statistics
import sys
import tempfile
import time

SMALL = os.environ.get("SMALL") == "1"
COMPILE_ONLY = os.environ.get("COMPILE_ONLY") == "1"
if SMALL or COMPILE_ONLY:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental.pallas.ops.tpu.megablox import gmm  # noqa: E402
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata  # noqa: E402

from jax_llama_tpu.ops.moe import _TILE_M, _block_bytes, _tiling  # noqa: E402

# D, F, experts, top_k, expert layers of the benchmark's file, the cell's
# slots and its `moe.max_load_share_pct` (ledger, PR 51).
CONFIGS = {
    "Xing4.0-29B-A4B": (3584, 1024, 64, 4, 5, 16, 3.6),
    "Trinity-Mini": (2048, 1024, 128, 8, 4, 8, 6.1),
    "kanana-2-30b-a3b": (2048, 768, 128, 6, 7, 8, 3.1),
    "Keye-VL-2.0-30B-A3B": (2048, 768, 128, 8, 6, 8, 9.8),
}
if SMALL:
    CONFIGS = {"tiny": (256, 128, 8, 2, 2, 4, 20.0)}
VMEM_LIMIT = 16 * 2 ** 20  # the scoped limit: no candidate above it is tried
CALLS = 2 if SMALL else 30


def ladder(k, n):
    """The tile before PR 52: the first of a fixed ladder that divides."""
    tk = next(t for t in (1024, 768, 512, 256, 128, k) if k % t == 0)
    tn = next(t for t in (512, 256, 128, n) if n % t == 0)
    return _TILE_M, tk, tn


def candidates(m, k, n):
    """The ladder's tile, the rule's, and the whole contraction and the
    ladder's `tk` under every width that divides N by 128s; the rule's
    `tk` and `tn` at 256 rows too where a chunk has several row tiles."""
    wide = [t for t in range(128, n + 1, 128) if n % t == 0 and t >= min(256, n)]
    out = [ladder(k, n), _tiling(k, n, jnp.bfloat16)]
    out += [(_TILE_M, tk, tn) for tk in (k, ladder(k, n)[1]) for tn in wide]
    if m > 2 * _TILE_M:
        out.append((2 * _TILE_M,) + out[1][1:])
    seen = []
    for t in out:
        if t not in seen and _block_bytes(*t, 2) <= VMEM_LIMIT:
            seen.append(t)
    return seen


def draw(rng, experts, pairs, share_pct):
    """Group sizes [experts] summing to `pairs`."""
    z = rng.standard_normal(experts)
    lo, hi = 0.0, 8.0
    for _ in range(40):
        s = (lo + hi) / 2
        p = np.exp(s * z)
        p /= p.sum()
        lo, hi = (s, hi) if p.max() * 100 < share_pct else (lo, s)
    return rng.multinomial(pairs, p).astype(np.int32)


def visits(sizes, m, tm):
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    inside = np.sum((sizes > 0) & (starts % tm != 0))
    return int(-(-m // tm) + inside)


def timed(fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / CALLS * 1e3


def kernel_ms(fns, *args, calls=3):
    """The Mosaic call's own device milliseconds for each of `fns`: one
    trace of `calls` calls of each in turn, the `gmm` events of the device's
    operations line in order of start, the median of each run of `calls`."""
    from benchmark import trace

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for fn in fns:
            for _ in range(calls):
                jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
        planes = trace.read_planes(path)
    ops = next(lines[trace.OPS_LINE] for name, lines in sorted(planes.items())
               if trace.is_device(name) and trace.OPS_LINE in lines)
    took = [(e - s) * 1e3 for name, s, e in sorted(ops, key=lambda ev: ev[1])
            if "gmm" in name.split(" = ")[0]]
    if len(took) != calls * len(fns):
        raise RuntimeError(f"{len(took)} gmm events for {len(fns)} x {calls} calls")
    return [statistics.median(took[i * calls:(i + 1) * calls]) for i in range(len(fns))]


def main(names):
    if COMPILE_ONLY:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
    rng = np.random.RandomState(52)
    lines = []
    for name in names or CONFIGS:
        D, F, E, top_k, L, slots, share = CONFIGS[name]
        for op, K, N in (("gate_up", D, 2 * F), ("down", F, D)):
            # one expert's draw for every expert: the kernel's time does not
            # read the values, and L * E draws take the host a minute a weight
            w = None if COMPILE_ONLY else jnp.tile(jnp.asarray(
                rng.standard_normal((1, K, N)) * 0.02, jnp.bfloat16), (L * E, 1, 1))
            for rows, pairs in (("decode", slots * top_k), ("chunk512", 512 * top_k),
                                ("chunk2048", 2048 * top_k)):
                m = -(-pairs // _TILE_M) * _TILE_M
                own = draw(rng, E, pairs, share)
                sizes = np.zeros(L * E, np.int32)
                sizes[(L // 2) * E:(L // 2 + 1) * E] = own
                head = {
                    "config": name, "op": op, "rows": rows, "m": m, "k": K, "n": N,
                    "max_share_pct": round(100 * own.max() / pairs, 2),
                    "touched": int((own > 0).sum()),
                }
                if not COMPILE_ONLY:
                    x = jnp.asarray(rng.standard_normal((m, K)), jnp.bfloat16)
                    g = jnp.asarray(sizes)
                ran = []
                tiles, was, now = candidates(m, K, N), ladder(K, N), _tiling(K, N, jnp.bfloat16)
                for tile in tiles:
                    fn = jax.jit(lambda x, w, g, t=tile: gmm(
                        x, w, g, preferred_element_type=jnp.bfloat16, tiling=t,
                        interpret=SMALL))
                    rec = dict(head, tile=list(tile), block_MB=round(_block_bytes(*tile, 2) / 1e6, 2),
                               visits=visits(own, m, tile[0]),
                               ladder=tile == was, rule=tile == now)
                    try:
                        if COMPILE_ONLY:
                            sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
                            fn.lower(sds((m, K), jnp.bfloat16), sds((L * E, K, N), jnp.bfloat16),
                                     sds((L * E,), jnp.int32)).compile()
                            rec["compiles"] = True
                        else:
                            rec["ms"] = round(timed(fn, x, w, g), 4)
                            ran.append((fn, rec))
                    except Exception as e:  # noqa: BLE001 - the compiler's refusal is a row of the table
                        rec["error"] = str(e).splitlines()[0][:160] if str(e) else type(e).__name__
                    lines.append(rec)
                if ran and not SMALL:   # a CPU trace has no device plane
                    try:
                        took = kernel_ms([fn for fn, _ in ran], x, w, g)
                    except (RuntimeError, StopIteration) as e:
                        print(json.dumps({"kernel_ms": repr(e)[:200]}), flush=True)
                        took = []
                    for (_, rec), ms in zip(ran, took):
                        rec.update(
                            kernel_ms=round(ms, 4),
                            weights_GBps=round(head["touched"] * K * N * 2 / ms / 1e6, 1),
                            tflops=round(2 * pairs * K * N / ms / 1e9, 2))
                for rec in lines[-len(tiles):]:
                    print(json.dumps(rec), flush=True)
            del w
            os.makedirs("chiprun_out", exist_ok=True)
            with open("chiprun_out/gmm_tiles.json", "w") as f:
                json.dump({"device": str(jax.devices()[0].device_kind),
                           "compile_only": COMPILE_ONLY, "lines": lines}, f, indent=0)


if __name__ == "__main__":
    main(sys.argv[1:])
