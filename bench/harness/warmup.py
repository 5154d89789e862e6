"""Compiles the engine's step programs before the window.

The engine keeps one jitted program per step shape (decode bucket, page
bucket, chunk bucket, horizon), made on first use; at these widths one takes
2 to 3 s to compile on a v5e host and under a second to load from the
persistent cache. Which shapes a cell's traffic reaches depends on how
batches form, so the shapes are a data file of the cell
(``bench/shapes/<cell>.json``), recorded on the chip from the cell's own
traffic over many seeds. Every run compiles, or loads, all of them and
every decode page bucket before its window, and nothing else, so that its
set-up does not depend on what earlier runs in the checkout did. It prints
how many shapes it met outside the cell's file and records them beside the
compile cache (for a later benchmark PR to commit). Those, and the small
programs of KV migration (one per transfer length), compile inside the
window and are counted by ``CompileCounter``.

Warming calls each program once with all-zero block tables: every KV write
lands on page 0, which the engine reserves as a trash page, so no request's
state changes. A shape the engine can no longer make is skipped and counted.
"""
from __future__ import annotations

import json
import logging
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

KINDS = {
    "decode": "_decode_fns",
    "horizon": "_horizon_fns",
    "mixed": "_mixed_fns",
    "mixed_horizon": "_mixed_horizon_fns",
}


def shapes_of(engine) -> list:
    """(kind, key) of every step program the engine has made."""
    out = []
    for kind, attr in KINDS.items():
        out += [[kind, list(k)] for k in getattr(engine, attr)]
    return sorted(out)


def record(engine, path: Path) -> int:
    """Adds the engine's shapes to the file at ``path``; returns its size."""
    old = json.loads(path.read_text()) if path.exists() else []
    new = sorted({json.dumps(s) for s in old + shapes_of(engine)})
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps([json.loads(s) for s in new]))
    tmp.replace(path)
    return len(new)


def planned(files: list, bucket: int, max_pages: int) -> list:
    """The shapes listed in ``files`` plus the decode step at every page
    bucket up to ``max_pages`` (the page buckets are powers of two)."""
    shapes = []
    for f in files:
        if f.exists():
            shapes += json.loads(f.read_text())
    p = 1
    while True:
        shapes.append(["decode", [bucket, p, False]])
        if p >= max_pages:
            break
        p *= 2
    return sorted({json.dumps(s): s for s in shapes}.values(),
                  key=json.dumps)


def outside(engine, cell_file: Path) -> int:
    """Shapes the engine made that the cell's list does not hold."""
    known = ({json.dumps(s) for s in json.loads(cell_file.read_text())}
             if cell_file.exists() else set())
    return sum(1 for s in shapes_of(engine) if json.dumps(s) not in known)


def warm(engine, shapes: list) -> int:
    """Runs each step program once; returns how many it could not make."""
    failed = 0
    for kind, k in shapes:
        try:
            fn, args = _program(engine, kind, k)
        except (AttributeError, TypeError, ValueError):
            failed += 1
            continue
        cache = engine.cache
        i = next(j for j, a in enumerate(args) if a is POOLS)
        out, cache.k_pool, cache.v_pool = fn(
            engine.params, *args[:i], cache.k_pool, cache.v_pool,
            *args[i + 1:])
        jax.block_until_ready(out)
    return failed


#: where the two donated KV pools go in a step's arguments
POOLS = "pools"


def _program(engine, kind: str, k: list):
    """The engine's jitted step for one shape, and all-zero arguments (the
    two KV pools marked by ``POOLS``) as the engine would pass them."""
    def z(*s):
        return jnp.asarray(np.zeros(s, np.int32))

    def f32(n):
        return jnp.asarray(np.zeros(n, np.float32))

    key, step = engine._base_key, np.int32(1)
    if kind == "decode":
        b, pages, sampled = k
        return engine._decode_fn(b, pages, sampled), (
            z(b), z(b), z(b, pages), z(b) + 1, POOLS, key, step, f32(b),
            z(b))
    if kind == "horizon":
        b, pages, steps, sampled = k
        return engine._horizon_fn(b, pages, steps, sampled), (
            z(b), z(b), z(b, pages), POOLS, z(b), key, step, f32(b), z(b))
    if kind == "mixed":
        b, pages, c, cp, sampled = k
        return engine._mixed_fn(b, pages, c, cp, sampled), (
            z(b), z(b), z(b, pages), z(b) + 1, z(c),
            jnp.asarray(np.array([0, 1], np.int32)), z(cp), POOLS, key, step,
            f32(b + 1), z(b + 1))
    if kind == "mixed_horizon":
        b, pages, c, cp, steps, sampled = k
        meta = jnp.asarray(np.tile(np.array([0, 1], np.int32), (steps, 1)))
        return engine._mixed_horizon_fn(b, pages, c, cp, steps, sampled), (
            z(b), z(b), z(b, pages), POOLS, z(b), z(steps, c), meta, z(cp),
            key, step, f32(b + 1), z(b + 1))
    raise ValueError(f"unknown step kind {kind!r}")


class CompileCounter:
    """Counts XLA compilations (and persistent-cache loads) with their host
    times, and the names of the functions compiled, from JAX's own
    monitoring events and compile log."""

    def __init__(self):
        self.compiles: list[tuple[float, float]] = []    # (end time, secs)
        self.names: list[tuple[float, str]] = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._event)
        self._log_compiles = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        self._handler = _NameHandler(self)
        self._loggers = [(lg, lg.propagate) for lg in map(logging.getLogger, (
            "jax._src.dispatch", "jax._src.interpreters.pxla",
            "jax._src.compiler"))]
        for lg, _ in self._loggers:
            lg.addHandler(self._handler)
            lg.propagate = False

    def close(self) -> None:
        """Stops counting and gives JAX's logging back as it was."""
        jax.monitoring.unregister_event_duration_listener(
            self._event)
        jax.config.update("jax_log_compiles", self._log_compiles)
        for lg, propagate in self._loggers:
            lg.removeHandler(self._handler)
            lg.propagate = propagate

    def _event(self, name: str, secs: float, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compiles.append((time.perf_counter(), secs))

    def between(self, t0: float, t1: float) -> tuple[int, float, dict]:
        with self._lock:
            c = [s for t, s in self.compiles if t0 <= t < t1]
            names: dict[str, int] = {}
            for t, n in self.names:
                if t0 <= t < t1:
                    names[n] = names.get(n, 0) + 1
        return len(c), float(sum(c)), names


class _NameHandler(logging.Handler):
    def __init__(self, counter: CompileCounter):
        super().__init__(logging.DEBUG)
        self.counter = counter

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Finished XLA compilation of "):
            name = msg[len("Finished XLA compilation of "):].split(" in ")[0]
            with self.counter._lock:
                self.counter.names.append((time.perf_counter(), name))
