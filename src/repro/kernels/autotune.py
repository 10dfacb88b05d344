"""Block-size autotuner for the fused W4A8 kernels.

Two halves:

  * ``autotune_gemm(build, key, ...)`` — the *offline* timed sweep: given a
    factory that builds a zero-arg kernel call for a (bm, bn) candidate, time
    every candidate on concrete inputs and persist the winner in a JSON
    cache. Run from the benchmark harness (or any warmup script with real
    tensors); it cannot run at dispatch time because the ops layer is called
    under jit traces where inputs are abstract.
  * ``best_block_sizes(...)`` — the *dispatch-time* lookup: pure cache read
    keyed on the GEMM signature, falling back to a shape heuristic on a miss
    (the kernels clamp blocks to divisors, so the heuristic is always legal).

Cache keys: kind | backend | E | M | N | K | w_fmt | a_fmt | group | m2 |
lorc_rank | transpose — everything that changes the kernel's tiling
economics. The cache file (REPRO_AUTOTUNE_CACHE, default
~/.cache/repro/w4a8_autotune.json) is invalidated simply by deleting it; a
schema version inside the file guards stale layouts across refactors.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, Iterable, Optional, Tuple

import jax

__all__ = [
    "cache_path",
    "clear_cache",
    "cache_key",
    "best_block_sizes",
    "autotune_gemm",
    "DEFAULT_CANDIDATES",
]

SCHEMA = 1

DEFAULT_CANDIDATES: Tuple[Tuple[int, int], ...] = (
    (128, 128), (256, 128), (128, 256), (256, 256), (128, 512), (256, 512),
    (64, 128), (128, 64), (64, 64), (32, 128), (16, 128), (8, 128), (8, 256),
)

_MEM: Optional[Dict[str, list]] = None  # in-process mirror of the JSON file


def cache_path() -> str:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "w4a8_autotune.json")


def _load() -> Dict[str, list]:
    global _MEM
    if _MEM is not None:
        return _MEM
    _MEM = {}
    try:
        with open(cache_path()) as f:
            data = json.load(f)
        if isinstance(data, dict) and data.get("__schema__") == SCHEMA:
            _MEM = {k: v for k, v in data.items() if not k.startswith("__")}
    except (OSError, ValueError):
        pass
    return _MEM


def _save() -> None:
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        payload = {"__schema__": SCHEMA}
        payload.update(_load())
        with open(path, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
    except OSError:
        pass  # read-only FS: the in-process cache still serves this run


def clear_cache() -> None:
    global _MEM
    _MEM = None
    try:
        os.remove(cache_path())
    except OSError:
        pass


def cache_key(
    kind: str,
    *,
    batch: int,
    m: int,
    n: int,
    k: int,
    w_fmt: str,
    a_fmt: Optional[str],
    group_size: int,
    m2: bool,
    lorc_rank: int,
    transpose_w: bool = False,
    backend: Optional[str] = None,
) -> str:
    backend = backend or jax.default_backend()
    return "|".join(str(v) for v in (
        kind, backend, batch, m, n, k, w_fmt, a_fmt or "none", group_size,
        int(m2), lorc_rank, int(transpose_w),
    ))


def _heuristic(kind: str, m: int, n: int) -> Tuple[int, int]:
    """Cache-miss default: full MXU tiles, shrunk for skinny decode batches
    (tiny M wastes no VMEM on a tall block; the kernel clamps to divisors).
    For the paged decode-attention kernel (kind 'decode_attn') bm is the
    query-group row block: the g = H/KV heads padded up to a sublane
    multiple; bn is the page size (the kv block is a whole page)."""
    if kind == "decode_attn":
        return max(8, -(-m // 8) * 8), n
    bm = 128 if m >= 128 else max(8, m)
    bn = 128
    return bm, bn


def best_block_sizes(kind: str = "fused", **sig) -> Tuple[int, int]:
    """Dispatch-time (bm, bn) choice. Safe under jit traces: pure lookup on
    static shapes, no timing, no device work."""
    key = cache_key(kind, **sig)
    hit = _load().get(key)
    if hit:
        return int(hit[0]), int(hit[1])
    return _heuristic(kind, sig["m"], sig["n"])


def autotune_gemm(
    build: Callable[[int, int], Callable[[], object]],
    key: str,
    candidates: Iterable[Tuple[int, int]] = DEFAULT_CANDIDATES,
    reps: int = 3,
    dims: Optional[Tuple[int, int]] = None,
) -> Tuple[int, int]:
    """Timed sweep: ``build(bm, bn)`` returns a zero-arg callable running the
    kernel on concrete inputs. The winner is persisted under ``key`` and
    returned. Candidates that fail to build/run are skipped.

    ``dims=(m, n)`` maps candidates through the kernels' divisor clamp
    first and dedupes — e.g. for a decode batch m=8 every bm >= 8 collapses
    to the same effective tiling, which would otherwise be compiled and
    timed once per raw candidate; the cached winner is then the *effective*
    pair, so dispatch reuses one jit variant."""
    mem = _load()
    if key in mem:
        return int(mem[key][0]), int(mem[key][1])
    if dims is not None:
        from .w4a8_fused import clamp_block

        m, n = dims
        seen = set()
        candidates = [c for c in
                      (clamp_block(m, n, bm, bn) for bm, bn in candidates)
                      if not (c in seen or seen.add(c))]
    # build + warm every runnable candidate first, then time them in
    # interleaved rounds taking per-candidate minima — sequential
    # median-per-candidate lets machine-load drift crown whichever candidate
    # happened to run during a quiet phase
    calls = {}
    last_exc: Optional[Exception] = None
    for bm, bn in candidates:
        try:
            call = build(bm, bn)
            jax.block_until_ready(call())  # compile + warm
            calls[(bm, bn)] = call
        except Exception as exc:  # noqa: BLE001 — illegal tiling for this shape
            last_exc = exc
            continue
    if not calls:
        # every candidate failed: that's a kernel bug, not a tiling issue —
        # surface the real traceback instead of burying it
        raise ValueError(f"no candidate block size ran for {key}") from last_exc
    times = {c: float("inf") for c in calls}
    for _ in range(reps):
        for c, call in calls.items():
            t0 = time.perf_counter()
            jax.block_until_ready(call())
            times[c] = min(times[c], time.perf_counter() - t0)
    best = min(times, key=times.get)
    mem[key] = [best[0], best[1], times[best] * 1e6]  # us, for the curious
    _save()
    return best
