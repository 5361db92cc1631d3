"""repro.mining.tune — kernel execution plans: real backend dispatch plus a
small persisted autotuner for the fused intersect kernel's block knobs.

Three layers of the stack meet here:

* **Backend registry.** ``MineSpec.backend`` used to be a string switch
  (``auto|pallas|jnp``) that silently accepted anything. The registry below
  is the single source of truth: user-facing names (``auto``, ``pallas``,
  ``jnp``, ``pallas-tpu``, ``pallas-gpu``, ``pallas-interpret``) resolve via
  :func:`resolve_backend` to a *concrete* backend for the current platform,
  or raise with the registered list. ``auto`` picks the fastest available
  path (Pallas on TPU/GPU, jnp elsewhere); ``pallas`` forces a Pallas
  lowering, falling back to the interpreter off-accelerator — which is what
  makes the masked early-stop kernel testable in CPU CI.

* **KernelPlan.** One frozen record of everything the execution layer needs
  to launch a wave: the resolved backend, the three block knobs, and the
  early-stop flag. ``HPrepostMiner`` resolves a plan per (candidate-count,
  nlist-width) and threads it into the wave jits as static arguments, so
  retuning never touches prep caches or snapshot keys (blocks are
  execution-only).

* **KernelTuner.** ``la_block/ly_block/batch_block`` were manual knobs; the
  tuner replaces the guess with a small timed search over block configs on
  first use per (backend, platform, width-bucket, batch-bucket), persisted
  as ``kernel_plans.json`` next to the ``SnapshotStore`` so every process on
  the mesh reruns its best config with zero search trials.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os
import threading
import time

import jax
import numpy as np

from repro.checkpoint.atomic import fsync_write

PLANS_SCHEMA = 1
PLANS_FILENAME = "kernel_plans.json"

# user-facing backend names -> how they resolve per platform. ``None`` means
# "not available here" and makes resolve_backend raise.
_REGISTRY: dict[str, dict[str, str | None]] = {
    "auto": {"tpu": "pallas-tpu", "gpu": "pallas-gpu", "*": "jnp"},
    "pallas": {"tpu": "pallas-tpu", "gpu": "pallas-gpu", "*": "pallas-interpret"},
    "jnp": {"*": "jnp"},
    "pallas-tpu": {"tpu": "pallas-tpu", "*": None},
    "pallas-gpu": {"gpu": "pallas-gpu", "*": None},
    "pallas-interpret": {"*": "pallas-interpret"},
}

# concrete backends an execution layer can actually be handed
PALLAS_BACKENDS = frozenset({"pallas-tpu", "pallas-gpu", "pallas-interpret"})


def registered_backends() -> list[str]:
    """Every name ``MineSpec.backend`` may carry."""
    return sorted(_REGISTRY)


def resolve_backend(name: str, platform: str | None = None) -> str:
    """Map a user-facing backend name to the concrete backend for this
    platform. Unknown names and unavailable backends raise ValueError."""
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown backend {name!r}; registered backends: "
            f"{', '.join(registered_backends())}"
        )
    platform = platform or jax.default_backend()
    table = _REGISTRY[name]
    resolved = table.get(platform, table.get("*"))
    if resolved is None:
        raise ValueError(
            f"backend {name!r} is not available on platform {platform!r} "
            f"(default backend: {jax.default_backend()!r})"
        )
    return resolved


def is_pallas(backend: str) -> bool:
    return backend in PALLAS_BACKENDS


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """Resolved execution config for one wave launch: a concrete backend,
    the intersect kernel's block knobs, and the early-stop flag. ``source``
    records where the blocks came from (``config`` = the HPrepostConfig
    defaults, ``tuned`` = fresh search, ``cached`` = persisted search)."""

    backend: str
    la_block: int
    ly_block: int
    batch_block: int
    early_stop: bool
    source: str = "config"


def static_plan(
    backend: str,
    la_block: int,
    ly_block: int,
    batch_block: int,
    early_stop: bool,
    platform: str | None = None,
) -> KernelPlan:
    """A plan straight from config knobs — no search, backend resolved."""
    return KernelPlan(
        backend=resolve_backend(backend, platform),
        la_block=la_block,
        ly_block=ly_block,
        batch_block=batch_block,
        early_stop=early_stop,
        source="config",
    )


def _bucket(n: int, lo: int, hi: int) -> int:
    """Smallest power of two >= n, clamped to [lo, hi] — plans are keyed
    and measured per bucket, not per exact shape."""
    n = max(int(n), 1)
    b = 1 << (n - 1).bit_length()
    return max(lo, min(hi, b))


def _synthetic_nlists(B: int, W: int) -> tuple[np.ndarray, ...]:
    """Timing fixtures: shape- and dtype-faithful PP-code batches. The
    kernel's cost is data-independent (dense mask contraction), so sorted
    random codes are as representative as real ones."""
    rng = np.random.default_rng(0)
    a_pre = np.sort(rng.integers(0, 1 << 20, (B, W)), axis=1).astype(np.int32)
    a_post = np.sort(rng.integers(0, 1 << 20, (B, W)), axis=1).astype(np.int32)
    y_pre = np.sort(rng.integers(0, 1 << 20, (B, W)), axis=1).astype(np.int32)
    y_post = np.sort(rng.integers(0, 1 << 20, (B, W)), axis=1).astype(np.int32)
    y_cnt = rng.integers(1, 8, (B, W)).astype(np.int32)
    a_cnt = rng.integers(1, 8, (B, W)).astype(np.int32)
    return a_pre, a_post, a_cnt, y_pre, y_post, y_cnt


class KernelTuner:
    """Timed block-config search with a cross-process JSON plan cache.

    ``plan_for`` is the only entry point: it buckets the requested shape,
    serves a persisted plan when one exists (``stats['trials']`` stays 0 —
    the property ``make tune-smoke`` asserts), and otherwise times a small
    cartesian search and persists the winner atomically.
    """

    # the TPU lowers a (bb, la) block only when bb is a multiple of 8 (or
    # the whole batch) and la a multiple of 128 (or the whole width)
    LA_CHOICES = (128, 256, 512)
    BB_CHOICES = (8, 16)

    def __init__(self, plan_dir: str | None = None, platform: str | None = None):
        self._dir = plan_dir
        self._platform = platform or jax.default_backend()
        self._plans: dict[str, dict] = {}
        self._lock = threading.Lock()
        self.stats = {
            "trials": 0,       # timed kernel launches this process
            "tuned": 0,        # keys searched this process
            "plan_hits": 0,    # keys served from memory/disk
            "loaded_plans": 0, # keys read from kernel_plans.json
        }
        if self._dir:
            self._load()
            self.stats["loaded_plans"] = len(self._plans)

    # ------------------------------------------------------------ persistence
    def _path(self) -> str:
        return os.path.join(self._dir, PLANS_FILENAME)

    def _load(self) -> None:
        try:
            with open(self._path(), "rb") as f:
                doc = json.loads(f.read().decode())
        except (FileNotFoundError, ValueError, OSError):
            return
        if doc.get("schema") != PLANS_SCHEMA:
            return
        self._plans.update(doc.get("plans", {}))

    def _save(self) -> None:
        if not self._dir:
            return
        os.makedirs(self._dir, exist_ok=True)
        doc = {"schema": PLANS_SCHEMA, "plans": self._plans}
        fsync_write(self._path(), json.dumps(doc, indent=1, sort_keys=True).encode())

    # ------------------------------------------------------------ the search
    def _key(self, backend: str, B: int, W: int, early_stop: bool) -> str:
        wb = _bucket(W, 8, 1024)
        bbk = _bucket(B, 8, 512)
        return f"{backend}|{self._platform}|es{int(early_stop)}|W{wb}|B{bbk}"

    def _measure_us(self, backend, B, W, la, ly, bb, early_stop, reps=3) -> float:
        from repro.kernels.nlist_intersect.ops import nlist_intersect

        arrs = _synthetic_nlists(B, W)
        a_pre, a_post, a_cnt, y_pre, y_post, y_cnt = arrs

        def launch():
            merged, sup = nlist_intersect(
                a_pre, a_post, y_pre, y_post, y_cnt,
                a_cnt=a_cnt, backend=backend,
                la_block=la, ly_block=ly, batch_block=bb,
                early_stop=early_stop, min_count=2 if early_stop else None,
            )
            jax.block_until_ready((merged, sup))

        launch()  # compile outside the timed region
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            launch()
            best = min(best, time.perf_counter() - t0)
            self.stats["trials"] += 1
        return best * 1e6

    def search_space(self, backend: str, B: int, W: int):
        """-> (B, W, [(la, bb), ...]): the bucketed fixture shape the search
        measures at (that is what the key promises) and the block configs it
        tries there. The interpreter is a python loop, so its fixture sizes
        are capped."""
        wb = _bucket(W, 8, 1024)
        bbk = _bucket(B, 8, 512)
        if backend == "pallas-interpret":
            wb, bbk = min(wb, 128), min(bbk, 32)
        la_opts = sorted({min(wb, c) for c in self.LA_CHOICES})
        bb_opts = sorted({min(bbk, c) for c in self.BB_CHOICES})
        return bbk, wb, list(itertools.product(la_opts, bb_opts))

    def _search(self, backend: str, B: int, W: int, early_stop: bool) -> dict:
        bbk, wb, configs = self.search_space(backend, B, W)
        best = None
        for la, bb in configs:
            us = self._measure_us(backend, bbk, wb, la, la, bb, early_stop)
            if best is None or us < best["best_us"]:
                best = {
                    "la_block": la, "ly_block": la, "batch_block": bb,
                    "best_us": round(us, 1),
                    "trials": len(configs),
                }
        return best

    # -------------------------------------------------------------- frontdoor
    def plan_for(
        self,
        *,
        backend: str,
        B: int,
        W: int,
        early_stop: bool,
        defaults: tuple[int, int, int] = (512, 512, 8),
        tune: bool = True,
    ) -> KernelPlan:
        resolved = resolve_backend(backend, self._platform)
        if resolved == "jnp" and not tune:
            # blocks are inert on the jnp path; skip even the dict lookup
            return KernelPlan(resolved, *defaults, early_stop, "config")
        key = self._key(resolved, B, W, early_stop)
        with self._lock:
            rec = self._plans.get(key)
            if rec is not None:
                self.stats["plan_hits"] += 1
                src = "cached"
            elif not tune:
                return KernelPlan(resolved, *defaults, early_stop, "config")
            else:
                rec = self._search(resolved, B, W, early_stop)
                self._plans[key] = rec
                self._save()
                self.stats["tuned"] += 1
                src = "tuned"
            return KernelPlan(
                backend=resolved,
                la_block=int(rec["la_block"]),
                ly_block=int(rec["ly_block"]),
                batch_block=int(rec["batch_block"]),
                early_stop=early_stop,
                source=src,
            )
