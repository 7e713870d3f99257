"""Persisted AOT executable cache for the serving engine.

PR 5's warmup sweep means no live request ever pays a compile — but
every fresh process pays the WHOLE sweep before ``assert_warm()``. For
scale-to-zero, fleet rollouts and version swaps that is the cold-start
bill: tracing the model's Python forward once per ladder bucket plus an
XLA compile per (bucket, target). This module persists both halves:

1. **StableHLO blobs** (``jax.export``): one serialized exported module
   per ladder bucket. Loading one skips re-tracing the model's Python
   layer stack — ``export.deserialize(blob).call`` is a thin wrapper
   whose own trace is O(1) in model depth.
2. **XLA executable cache**: the backend compile of each bucket
   (including the blob-wrapper's signature, which is primed at save
   time) lands in JAX's persistent compilation cache, wherever the
   process keeps it — ``JAX_COMPILATION_CACHE_DIR`` or the package's
   fixed in-checkout directory (``deeplearning4j_tpu/__init__.py``);
   this module never redirects it. Its entries are keyed by the
   computation fingerprint + jaxlib version + backend, so a stale entry
   can never be served — it just misses.

A ``manifest.json`` fingerprints what the blobs were exported from:
model version + weights digest, parameter tree spec, jax/jaxlib
versions, backend platform/device kind, the serving contract
(feature_shape, dtype, ladder, precision, calibration hash).
``try_load`` compares field by field and falls through to live compile
on ANY mismatch (recording which field diverged — for the precision /
calibration fields the reason carries both values, so a rejected quant
cache explains itself) — a cache can make a cold start fast, never
wrong. Mesh-sharded (multi-replica full-bucket) executables are not
exported; they fall through to live compile and still benefit from the
XLA cache half.

Format 2 manifests hold one entry PER PRECISION: f32, bf16 and int8
executables of the same model coexist in one cache dir as first-class
``entries[<precision>]`` rows with per-precision blob filenames, and a
lookup only ever consults its own precision's entry — a quantized blob
can never satisfy an f32 lookup (their fingerprints differ in
``serving.precision``, ``serving.calibration`` AND ``weights_sha256``,
since int8 committed params are different bytes) nor vice versa.

Layout on disk::

    <cache_dir>/manifest.json          per-precision fingerprints + buckets
    <cache_dir>/bucket_<N>.<precision>.stablehlo   exported modules
"""

from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path
from typing import Any, Dict, Optional

import numpy as np
from jax import export

from deeplearning4j_tpu.chaos.hook import chaos_site

MANIFEST = "manifest.json"
FORMAT_VERSION = 2          # 2: per-precision entries + calibration hash


def _tree_spec(params) -> list:
    """Stable description of a pytree's structure + leaf shapes/dtypes
    (metadata only — no device reads)."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(params)
    spec = []
    for a in leaves:
        dt = getattr(a, "dtype", None)
        spec.append([list(np.shape(a)),
                     str(dt) if dt is not None else type(a).__name__])
    return [str(treedef), spec]


def weights_digest(params) -> str:
    """sha256 over every leaf's bytes — the model-version key. One-time
    device→host read at engine start (cache setup), not a hot path."""
    import jax
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(params):
        a = np.asarray(leaf)  # host-sync-ok: one-time startup fingerprint fetch, pre-traffic
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def fingerprint(params, mstate, *, feature_shape, dtype, ladder,
                precision: str = "f32",
                calibration: Optional[str] = None,
                bf16: Optional[bool] = None,
                model_version: Optional[str] = None) -> Dict:
    """Everything a loaded executable's validity depends on.

    ``precision`` is the PrecisionPolicy tag (f32/bf16/int8) and
    ``calibration`` the int8 calibration provenance hash
    (QuantizedModel.calibration_hash()) — both are load-bearing: a
    quant entry must never satisfy an f32 lookup, and a re-calibrated
    model must never be served from stale-scale executables. ``bf16=``
    is the pre-PrecisionPolicy spelling, kept for old callers."""
    import jax
    import jaxlib
    if bf16 is not None:
        precision = "bf16" if bf16 else "f32"
    dev = jax.devices()[0]
    return {
        "format_version": FORMAT_VERSION,
        "model_version": model_version,
        "weights_sha256": weights_digest(params),
        "params_spec": _tree_spec(params),
        "model_state_spec": _tree_spec(mstate),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": {"platform": dev.platform,
                    "device_kind": dev.device_kind},
        "serving": {"feature_shape": list(feature_shape),
                    "dtype": str(np.dtype(dtype)),
                    "ladder": list(ladder),
                    "precision": str(precision),
                    "calibration": calibration},
    }


def _first_mismatch(want: Dict, got: Dict, prefix: str = "") -> Optional[str]:
    for k in want:
        w, g = want[k], got.get(k)
        if isinstance(w, dict) and isinstance(g, dict):
            sub = _first_mismatch(w, g, f"{prefix}{k}.")
            if sub:
                return sub
        elif w != g:
            return f"{prefix}{k}"
    return None


def _dig(d: Dict, dotted: str):
    for part in dotted.split("."):
        if not isinstance(d, dict):
            return None
        d = d.get(part)
    return d


def _mismatch_reason(fp: Dict, got_fp: Dict, diff: str) -> str:
    """Human-readable mismatch: always names the diverged field; for
    scalar fields (notably ``serving.precision`` and
    ``serving.calibration``) it also shows both values, so a rejected
    quant cache states exactly WHICH precision/calibration it held."""
    want_v, got_v = _dig(fp, diff), _dig(got_fp, diff)
    if all(isinstance(v, (str, int, float, bool, type(None)))
           for v in (want_v, got_v)):
        def short(v):
            s = repr(v)
            return s[:20] + "..." if len(s) > 23 else s
        return (f"fingerprint field {diff!r} diverged "
                f"(want {short(want_v)}, got {short(got_v)})")
    return f"fingerprint field {diff!r} diverged"


class AOTExecutableCache:
    """One serving engine's view of a persisted executable table.

    ``state`` after construction + ``try_load``:

    - ``"warm"``      manifest matched; blobs deserialized and in use
    - ``"cold"``      no manifest yet (first process; ``save`` fills it)
    - ``"mismatch"``  manifest found but the fingerprint diverged —
      ``reason`` names the first differing field; live compile is used
      (and ``save`` rewrites the cache for the new fingerprint)
    """

    def __init__(self, cache_dir: str):
        self.dir = Path(cache_dir)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.state = "cold"
        self.reason: Optional[str] = None
        self.hits = 0            # buckets served from a loaded blob
        self.misses = 0          # buckets that fell through to live trace
        self.quarantined = 0     # blobs failing their content checksum
        self._chaos_save = chaos_site("store.save")

    @staticmethod
    def _precision_of(fp: Dict) -> str:
        return str(fp.get("serving", {}).get("precision", "f32"))

    @staticmethod
    def _blob_name(bucket, precision: str) -> str:
        return f"bucket_{bucket}.{precision}.stablehlo"

    # ---- load ------------------------------------------------------------
    def try_load(self, fp: Dict) -> Dict[int, Any]:
        """Deserialized ``Exported`` per bucket when the manifest's
        entry FOR THIS PRECISION matches ``fp``; {} otherwise
        (state/reason record why). Other precisions' entries are
        invisible to the lookup — they can neither satisfy nor
        invalidate it."""
        path = self.dir / MANIFEST
        if not path.exists():
            self.state = "cold"
            return {}
        try:
            manifest = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError) as e:
            self.state = "mismatch"
            self.reason = f"unreadable manifest: {e}"
            return {}
        precision = self._precision_of(fp)
        entries = manifest.get("entries")
        if entries is None:
            # format-1 manifest (single fingerprint, pre-precision):
            # diff against its flat fingerprint so the reason names the
            # real divergence (format_version at minimum); save()
            # rewrites it as format 2
            entry = {"fingerprint": manifest.get("fingerprint", {}),
                     "buckets": []}
        else:
            entry = entries.get(precision)
            if entry is None:
                self.state = "cold"
                self.reason = (f"no {precision!r} entry (cache holds "
                               f"{sorted(entries)})")
                return {}
        got_fp = entry.get("fingerprint", {})
        diff = _first_mismatch(fp, got_fp)
        if diff is not None:
            self.state = "mismatch"
            self.reason = _mismatch_reason(fp, got_fp, diff)
            return {}
        loaded: Dict[int, Any] = {}
        checksums = entry.get("checksums") or {}
        for bucket in entry.get("buckets", []):
            blob_path = self.dir / self._blob_name(bucket, precision)
            try:
                raw = blob_path.read_bytes()
                want = checksums.get(str(bucket))
                if want is not None and \
                        hashlib.sha256(raw).hexdigest() != want:
                    # torn or bit-rotted blob: quarantine it and fall
                    # through to live compile — a warming node must
                    # NEVER crash (or serve garbage) on store corruption
                    self._quarantine(blob_path, bucket, "checksum")
                    continue
                loaded[int(bucket)] = export.deserialize(bytearray(raw))
            except Exception as e:
                # one bad blob falls through to live compile; the rest
                # of the table still loads
                self.misses += 1
                self.reason = f"bucket {bucket}: {type(e).__name__}"
        self.state = "warm" if loaded else "mismatch"
        return loaded

    def _quarantine(self, blob_path: Path, bucket, why: str) -> None:
        """Move a corrupt blob aside (``.quarantine`` suffix) so later
        loads don't re-pay the checksum failure and a later ``save``
        republishes a clean blob under the original name."""
        self.misses += 1
        self.quarantined += 1
        self.reason = f"bucket {bucket}: quarantined ({why})"
        try:
            os.replace(blob_path,
                       str(blob_path) + ".quarantine")
        except OSError:
            pass
        try:
            from deeplearning4j_tpu.observe.registry import (
                default_registry)
            default_registry().counter(
                "dl4j_aot_quarantined_total",
                "corrupt AOT cache blobs moved aside (content checksum "
                "or deserialize failure); each falls through to live "
                "compile").inc(1.0, bucket=str(bucket), reason=why)
        except Exception:
            pass

    # ---- save ------------------------------------------------------------
    def save(self, jit_fn, committed, fp: Dict, ladder, example) -> int:
        """Export + serialize one module per ladder bucket and prime the
        XLA cache under the blob-wrapper's compile key, then write the
        manifest (atomically, last — a crash mid-save leaves a cache
        that simply misses). Only THIS precision's entry is replaced;
        sibling precisions keep theirs (each entry's fingerprint is
        self-contained, so a stale sibling just misses at its own
        load). Returns the number of buckets saved."""
        import jax
        precision = self._precision_of(fp)
        params, mstate = committed
        saved = []
        checksums: Dict[str, str] = {}
        for bucket in ladder:
            x = np.zeros((int(bucket),) + tuple(example.shape[1:]),
                         example.dtype)
            try:
                exp = export.export(jit_fn)(params, mstate, x)
                blob = bytes(exp.serialize())
                # checksum of the TRUE bytes: corruption between save
                # and load (torn write, bit rot — or an armed chaos
                # plan mangling the write below) is caught at load
                checksums[str(int(bucket))] = hashlib.sha256(
                    blob).hexdigest()
                if self._chaos_save is not None:
                    blob, _ = self._chaos_save.mangle(blob, arg="blob")
                (self.dir / self._blob_name(bucket,  # graftlint: disable=atomic-write: blob bytes are sha256-checksummed and only become visible through the manifest's atomic os.replace; a torn blob quarantines at load
                                            precision)).write_bytes(blob)
                # prime: the loading process compiles jit(exp.call), a
                # different cache key than jit_fn's — pay it here, once,
                # so the fresh process's compile is a disk hit
                jax.jit(exp.call).lower(params, mstate, x).compile()  # graftlint: disable=recompile-hazard: one-time per-bucket cache-priming compile at save, not a live path
                saved.append(int(bucket))
            except Exception:
                continue        # that bucket warms live on load; rest save
        if saved:
            entries: Dict[str, Any] = {}
            try:
                manifest = json.loads((self.dir / MANIFEST).read_text())
                # format-1 manifests are superseded wholesale
                entries = dict(manifest.get("entries") or {})
            except Exception:
                pass
            entries[precision] = {"fingerprint": fp, "buckets": saved,
                                  "checksums": checksums}
            data = json.dumps(
                {"format_version": FORMAT_VERSION, "entries": entries},
                indent=2).encode("utf-8")
            if self._chaos_save is not None:
                data, _ = self._chaos_save.mangle(data, arg="manifest")
            tmp = self.dir / (MANIFEST + ".tmp")
            tmp.write_bytes(data)
            os.replace(tmp, self.dir / MANIFEST)
        return len(saved)

    def stats(self) -> Dict[str, Any]:
        return {"state": self.state, "reason": self.reason,
                "hits": self.hits, "misses": self.misses,
                "quarantined": self.quarantined,
                "dir": str(self.dir)}


class ArtifactStore:
    """Object-store bucket layout over the manifest format: one shared
    root holding one AOT cache dir per model key, so N serving nodes
    warm from ONE saved sweep with zero live compiles.

    Layout (local filesystem today, the key/object split maps 1:1 onto
    a GCS/S3 bucket later)::

        <root>/objects/<key>/manifest.json
        <root>/objects/<key>/bucket_<N>.<precision>.stablehlo

    Concurrency relies on the cache's own discipline: the manifest is
    written atomically and LAST (a reader mid-save just misses), every
    entry is self-fingerprinted (a stale or foreign entry can never be
    served), and the sweep is bitwise-deterministic cross-process — so
    the first node to finish its sweep publishes, and every later node
    (or rejoiner) gets a warm start. No locks, no coordinator."""

    def __init__(self, root: str):
        self.root = Path(root)
        (self.root / "objects").mkdir(parents=True, exist_ok=True)

    @staticmethod
    def _safe_key(key: str) -> str:
        import re
        safe = re.sub(r"[^A-Za-z0-9._-]", "_", str(key))
        if not safe or safe in (".", ".."):
            raise ValueError(f"unusable artifact key {key!r}")
        return safe

    def cache_dir(self, key: str) -> str:
        """The AOT cache dir for ``key`` (created if absent) — pass it
        straight to a ServingEngine's ``aot_cache_dir``."""
        d = self.root / "objects" / self._safe_key(key)
        d.mkdir(parents=True, exist_ok=True)
        return str(d)

    def keys(self) -> list:
        base = self.root / "objects"
        return sorted(p.name for p in base.iterdir() if p.is_dir())

    def manifest(self, key: str) -> Optional[Dict[str, Any]]:
        path = (self.root / "objects" / self._safe_key(key) / MANIFEST)
        try:
            return json.loads(path.read_text())
        except (OSError, json.JSONDecodeError):
            return None

    def stats(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"root": str(self.root), "keys": {}}
        for key in self.keys():
            m = self.manifest(key)
            entries = (m or {}).get("entries") or {}
            out["keys"][key] = {
                "published": m is not None,
                "precisions": {p: len(e.get("buckets", []))
                               for p, e in entries.items()},
            }
        return out
