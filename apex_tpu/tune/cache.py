"""Persistent kernel-tuning cache — the storage half of apex_tpu.tune.

One JSON file holds every tuned kernel config, grouped by DEVICE KIND
(a config tuned on v5e must never drive a v4 or CPU run).  Layout:

    {
      "schema": 1,
      "entries": {
        "<device-kind>": {
          "<op>|k1=v1,k2=v2,...": {
            "config": {"block_q": 512, ...},     # what tuned() returns
            "meta":   {"ms": 1.23, "when": ...}  # provenance, ignored
          }
        }
      }
    }

Path resolution: $APEX_TPU_TUNE_CACHE if set, else
``~/.cache/apex_tpu/tune.json``.  A missing, unreadable, corrupt, or
wrong-schema file degrades to an EMPTY cache (warn once) — lookups then
fall through to the committed defaults (defaults.py) and finally to each
kernel's deterministic heuristic, so a broken cache can never change
numerics or crash a run, only lose tuned speed.

``lookup`` is a pure host-side dict access at TRACE time: it adds zero
collectives and no host syncs inside jitted steps.  ``record``/``save``
are for the OFFLINE search driver (tune.search) only — never time or
write inside a jitted step.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import threading
import warnings
from typing import Any, Dict, Optional

SCHEMA_VERSION = 1

ENV_CACHE_PATH = "APEX_TPU_TUNE_CACHE"
ENV_DISABLE = "APEX_TPU_TUNE"          # "0" disables all lookups

_DEVICE_ALIASES = (
    # (substring of jax device_kind, canonical cache key)
    ("v5 lite", "v5e"),
    ("v5litepod", "v5e"),
    ("v5e", "v5e"),
    ("v6 lite", "v6e"),
    ("v6e", "v6e"),
    ("v5p", "v5p"),
    ("v4", "v4"),
)

_lock = threading.RLock()
_state: Dict[str, Any] = {
    "cache": None,         # loaded {key: {"config": ...}} for device kind
    "kind": None,
    "fingerprint": None,   # memoized digest of `cache` (logged per step)
    "hits": 0,
    "misses": 0,
    "warned": set(),
}


def cache_path() -> str:
    env = os.environ.get(ENV_CACHE_PATH)
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "apex_tpu",
                        "tune.json")


def device_kind() -> str:
    """Canonical device-kind key for the current default backend.

    TPU kinds are normalized through _DEVICE_ALIASES so "TPU v5 lite"
    and "TPU v5e" both tune/look up under "v5e"; non-TPU backends use
    the backend name ("cpu", "gpu") so CPU CI can exercise the cache
    machinery without ever matching TPU entries.
    """
    import jax

    from apex_tpu.ops._common import on_chip

    if not on_chip():
        return jax.default_backend()
    kind = jax.devices()[0].device_kind.lower()
    for sub, canon in _DEVICE_ALIASES:
        if sub in kind:
            return canon
    return kind.replace(" ", "-")


def make_key(op: str, attrs: Dict[str, Any]) -> str:
    """Canonical string key: op + sorted k=v attrs (ints/bools/strs)."""
    def fmt(v):
        if isinstance(v, bool):
            return "1" if v else "0"
        return str(v)

    items = ",".join(f"{k}={fmt(v)}" for k, v in sorted(attrs.items()))
    return f"{op}|{items}"


def _warn_once(tag: str, msg: str) -> None:
    if tag not in _state["warned"]:
        _state["warned"].add(tag)
        warnings.warn(msg, stacklevel=3)


def _read_file(path: str) -> Dict[str, Dict[str, Any]]:
    """All device-kind sections of the cache file; {} on any problem."""
    try:
        with open(path) as f:
            raw = json.load(f)
    except FileNotFoundError:
        return {}
    except (OSError, ValueError) as e:
        _warn_once("corrupt:" + path,
                   f"apex_tpu.tune: ignoring unreadable/corrupt cache "
                   f"{path} ({e!r}); falling back to heuristics")
        return {}
    if not isinstance(raw, dict) or raw.get("schema") != SCHEMA_VERSION:
        _warn_once("schema:" + path,
                   f"apex_tpu.tune: cache {path} has schema "
                   f"{raw.get('schema') if isinstance(raw, dict) else '?'}"
                   f" != {SCHEMA_VERSION}; ignoring it")
        return {}
    entries = raw.get("entries")
    return entries if isinstance(entries, dict) else {}


def _merged_for_kind(kind: str) -> Dict[str, Any]:
    """User-cache entries layered over the committed defaults."""
    from apex_tpu.tune import defaults

    merged = dict(defaults.DEFAULTS.get(kind, {}))
    file_entries = _read_file(cache_path()).get(kind, {})
    if isinstance(file_entries, dict):
        merged.update(file_entries)
    return merged


def _ensure_loaded() -> Dict[str, Any]:
    kind = device_kind()
    with _lock:
        if _state["cache"] is None or _state["kind"] != kind:
            from apex_tpu.monitor.compile import startup

            with startup.span("tune.load_tables"):
                _state["cache"] = _merged_for_kind(kind)
            _state["kind"] = kind
            _state["fingerprint"] = None
        return _state["cache"]


def lookup(op: str, attrs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Tuned config dict for (op, attrs) on the current device kind, or
    None (→ caller uses its heuristic).  Counts hits/misses for the
    bench fingerprint.  Pure host-side; safe at trace time."""
    if os.environ.get(ENV_DISABLE, "") == "0":
        return None
    cache = _ensure_loaded()
    entry = cache.get(make_key(op, attrs))
    with _lock:
        if entry is None:
            _state["misses"] += 1
            return None
        _state["hits"] += 1
    cfg = entry.get("config")
    return dict(cfg) if isinstance(cfg, dict) else None


@contextlib.contextmanager
def _file_lock(path: str):
    """Advisory cross-PROCESS lock for the cache read-modify-write —
    the in-process threading lock cannot stop two concurrent sweep
    processes from losing each other's entries.  Best-effort: platforms
    without fcntl (or a filesystem refusing flock) fall back to the
    unlocked write rather than failing the sweep."""
    lock_path = path + ".lock"
    f = None
    try:
        try:
            import fcntl
            f = open(lock_path, "w")
            fcntl.flock(f, fcntl.LOCK_EX)
        except Exception:
            f = None
        yield
    finally:
        if f is not None:
            try:
                import fcntl
                fcntl.flock(f, fcntl.LOCK_UN)
            except Exception:
                pass
            f.close()


def record(op: str, attrs: Dict[str, Any], config: Dict[str, Any],
           meta: Optional[Dict[str, Any]] = None,
           kind: Optional[str] = None) -> str:
    """Write one tuned entry to the cache file (read-modify-write under
    an advisory file lock, so concurrent sweep processes compose).
    Returns the key.  OFFLINE only — never call inside a jitted step."""
    kind = kind or device_kind()
    key = make_key(op, attrs)
    path = cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with _lock, _file_lock(path):
        entries = _read_file(path)
        entries.setdefault(kind, {})[key] = {
            "config": dict(config), "meta": dict(meta or {})}
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"schema": SCHEMA_VERSION, "entries": entries}, f,
                      indent=1, sort_keys=True)
        os.replace(tmp, path)
        invalidate()
    return key


def fingerprint() -> str:
    """12-hex digest of the ACTIVE merged entries (committed defaults +
    user cache for the current device kind) — stamps bench JSON and
    monitor records so two runs' tuned configs are comparable.
    Memoized until invalidate() (MetricsLogger reads it every record)."""
    cache = _ensure_loaded()
    with _lock:
        if _state["fingerprint"] is None:
            if not cache:
                _state["fingerprint"] = "empty"
            else:
                blob = json.dumps(cache, sort_keys=True).encode()
                _state["fingerprint"] = hashlib.sha1(blob).hexdigest()[:12]
        return _state["fingerprint"]


def stats() -> Dict[str, Any]:
    """{"hits", "misses", "fingerprint"} since the last reset — the
    tuner state stamp for bench.py / monitor."""
    with _lock:
        return {"hits": _state["hits"], "misses": _state["misses"],
                "fingerprint": fingerprint()}


def reset_stats() -> None:
    with _lock:
        _state["hits"] = 0
        _state["misses"] = 0


def invalidate() -> None:
    """Drop the in-memory memo (tests; after record/env changes)."""
    with _lock:
        _state["cache"] = None
        _state["kind"] = None
        _state["fingerprint"] = None
