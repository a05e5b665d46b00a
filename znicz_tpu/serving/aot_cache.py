"""Persisted AOT executable cache: compile-free cold starts (round 23).

Every warmed path in the framework is zero-compile, but every cold
*process* still pays the full trace+compile ladder — an elastic
restart recompiles the training step on the surviving mesh, a
fleet/pool scale-out onto a fresh host recompiles every bucket program
before it can absorb the burst it was spawned for.  This module turns
restart-to-first-token and resume-to-first-step from compile-bound
into I/O-bound: compiled XLA executables are serialized
(``jax.experimental.serialize_executable``) into a content-addressed
store next to the weights and deserialized before tracing on the next
cold start.

Safety model — a wrong program can NEVER load:

- entries are **content-addressed**: the key is a sha256 over every
  input that shapes the compiled program — program family, the
  bundle's architecture digest (manifest layer table + geometry +
  dtype, weight VALUES excluded: since round 13 weights are call-time
  operands, so a v2 weight refresh of the same architecture reuses v1
  programs), bucket/geometry, operand shapes + dtypes + shardings
  (which carry the mesh shape and axis names), donation, platform +
  device kind + device count, jax version, a digest of the znicz
  package sources, and a digest of the program-relevant config tree.
  Any mismatch is a plain cache miss → trace as before;
- jit-region programs additionally key on the **jaxpr hash** of the
  exact function being compiled (region bodies bake unit hyperparams
  into the trace as constants — no structural key can enumerate them,
  the jaxpr is the ground truth of what would be compiled);
- every entry carries a ``.sha256`` sidecar; a payload that fails
  digest verification (or fails to unpickle/deserialize) is
  **quarantined** (renamed aside, never retried) and the site falls
  back to tracing — counted as
  ``znicz_aot_cache_total{outcome="corrupt"}`` +
  ``znicz_recoveries_total{kind="aotcache_fallback"}``.  The
  ``aotcache.corrupt`` chaos site rots the payload bytes on read to
  drill exactly this path.

Enablement: ``root.common.engine.aot_cache`` — a directory path, or
``True`` (default directory under the snapshots dir), or ``False``
(hard opt-out, beats the environment).  When the config tree carries
no decision, the ``ZNICZ_AOT_CACHE`` environment variable supplies the
directory (the test suite's session fixture and fresh subprocesses use
this: the config tree is reset per test / empty at process start, the
environment survives both).  Unset everywhere = disabled, and every
compile site behaves exactly as it did before this round.

Publication (the fleet path): :func:`publish_programs` packs the
active cache's entries for one bundle architecture into
``<prefix>_v<version>.programs.npz`` (+ ``.sha256``) beside the
published weights; ``PublicationWatcher.poll`` imports a verified pack
into the local cache before surfacing the bundle — a scale-out replica
or hot-swap candidate comes up compile-free.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading

import numpy as np

from znicz_tpu.observe import metrics as _metrics
from znicz_tpu.utils.config import root
from znicz_tpu.utils.logger import Logger

__all__ = ["AotCache", "active_cache", "entry_key", "jaxpr_key",
           "program_digest", "build_digest", "config_digest",
           "guard_donated", "publish_programs", "import_programs",
           "status"]

#: default size bound for the store (evicts oldest entries past this)
DEFAULT_MAX_BYTES = 2 << 30

_lock = threading.Lock()
_build_digest: str | None = None
_caches: dict[str, "AotCache"] = {}
#: hits of JAX's own persistent compilation cache seen by this process
_jax_cache_hits = 0
_counting_jax_cache_hits = False


def _count_jax_cache_hits() -> None:
    """Listen (once per process) for hits of JAX's persistent
    compilation cache: an executable it hands back must not enter this
    store — see :meth:`AotCache.put`."""
    global _counting_jax_cache_hits
    if _counting_jax_cache_hits:     # (called under ``_lock`` too)
        return
    _counting_jax_cache_hits = True
    import jax

    def on_event(event: str, **_kwargs) -> None:
        global _jax_cache_hits
        if event == "/jax/compilation_cache/cache_hits":
            _jax_cache_hits += 1

    jax.monitoring.register_event_listener(on_event)


# ----------------------------------------------------------------------
# key material
# ----------------------------------------------------------------------
def build_digest() -> str:
    """sha256 over the znicz_tpu package sources, computed once per
    process — two processes agree on a key only when they run the same
    code, so a stale-code hit is impossible."""
    global _build_digest
    if _build_digest is None:
        import znicz_tpu
        pkg = os.path.dirname(os.path.abspath(znicz_tpu.__file__))
        h = hashlib.sha256()
        for base, dirs, files in sorted(os.walk(pkg)):
            dirs.sort()
            for name in sorted(files):
                if not name.endswith(".py"):
                    continue
                path = os.path.join(base, name)
                h.update(os.path.relpath(path, pkg).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
        _build_digest = h.hexdigest()[:16]
    return _build_digest


def platform_fingerprint() -> tuple:
    """(jax version, platform, device kind, device count) — the
    executable's hardware/runtime identity."""
    import jax
    devs = jax.devices()
    return (jax.__version__, devs[0].platform,
            getattr(devs[0], "device_kind", "?"), len(devs))


#: engine keys that never shape a compiled program (control-plane,
#: injection and cache knobs) — excluded so flipping them cannot fork
#: the key space.  Everything else IS included: an unknown new knob
#: then forks the cache (a false miss — safe), never a false hit.
_NONPROGRAM_ENGINE_KEYS = frozenset({
    "aot_cache", "aot_cache_bytes", "faults",
    "publish_fence_timeout_s", "swap_guard_margin",
    "swap_probation_steps", "read_backoff_s",
})


def config_digest() -> str:
    """Digest of the program-relevant config: global knobs (precision
    mode, bf16 activations, fp8 matmul, partition rules, serving
    donation, …) alter what a trace produces, and the test suite
    resets the tree per test — the digest keeps differently-configured
    programs in different entries."""
    def snap(node):
        as_dict = getattr(node, "as_dict", None)
        d = as_dict() if callable(as_dict) else dict(node or {})
        return {str(k): v for k, v in d.items()}

    common = root.common
    payload = {
        "precision": str(common.get("precision_type", "float32")),
        "engine": {k: v for k, v in snap(common.engine).items()
                   if k not in _NONPROGRAM_ENGINE_KEYS},
        "serving": snap(common.serving),
    }
    text = json.dumps(payload, sort_keys=True, default=repr)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def program_digest(manifest: dict) -> str:
    """Architecture digest of an exported bundle: every
    program-shaping manifest field (layer table + configs, input
    geometry, dtype, kind/sequence/decode metadata, quant key set) —
    but NOT the weight values, which are call-time operands, and NOT
    the volatile quant calibration record, so a recalibrated republish
    of the same architecture still hits."""
    m = json.loads(json.dumps(manifest, default=str, sort_keys=True))
    quant = m.get("quant")
    if isinstance(quant, dict):
        m["quant"] = {k: v for k, v in sorted(quant.items())
                      if not str(k).startswith("calib")}
    text = json.dumps(m, sort_keys=True)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _leaf_token(leaf) -> str:
    dtype = getattr(leaf, "dtype", None)
    return (f"{tuple(np.shape(leaf))}:"
            f"{np.dtype(dtype) if dtype is not None else '?'}:"
            f"{getattr(leaf, 'sharding', None)!r}")


def struct_token(structs) -> str:
    """Fingerprint of an operand pytree: tree structure + per-leaf
    shape/dtype/sharding (a NamedSharding's repr carries the mesh
    shape and axis names — the executable is pinned to them)."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(structs)
    return f"{treedef}|" + ";".join(_leaf_token(leaf) for leaf in leaves)


def entry_key(family: str, *, digest: str = "", geometry=(),
              structs=None, donate=False, extra=()) -> str:
    """The content address of one executable: sha256 over every input
    that shapes the compiled program."""
    fields = {
        "family": str(family),
        "digest": str(digest),
        "geometry": [str(g) for g in geometry],
        "structs": "" if structs is None else struct_token(structs),
        "donate": bool(donate),
        "extra": [str(e) for e in extra],
        "platform": [str(p) for p in platform_fingerprint()],
        "build": build_digest(),
        "config": config_digest(),
    }
    text = json.dumps(fields, sort_keys=True)
    return hashlib.sha256(text.encode()).hexdigest()


def jaxpr_key(fn, leaves, extra=()) -> str | None:
    """Key a jit-region program by the hash of its jaxpr.

    Region bodies bake unit hyperparameters (learning rate, momentum,
    dropout ratio, …) into the traced program as literals and closure
    constants — no enumerable structural key can cover them, so the
    key IS the trace: jaxpr text + closure-constant bytes + operand
    avals + the variant/donation tags in ``extra``.  Identical jaxpr
    ⇒ identical compiled program; the hit path therefore still traces
    (to compute the key) but skips the XLA compile — which is where
    nearly all the wall-clock lives.  Returns ``None`` when the
    function cannot be traced or hashed safely (caching is then simply
    skipped for this program)."""
    try:
        import jax
        closed = jax.make_jaxpr(fn)(*leaves)
        h = hashlib.sha256()
        h.update(str(closed.jaxpr).encode())
        for const in closed.consts:
            arr = np.asarray(const)
            h.update(str(arr.dtype).encode())
            h.update(str(arr.shape).encode())
            h.update(arr.tobytes())
        for leaf in leaves:
            h.update(_leaf_token(leaf).encode())
        for e in extra:
            h.update(str(e).encode())
        for p in platform_fingerprint():
            h.update(str(p).encode())
        h.update(build_digest().encode())
        h.update(config_digest().encode())
        return h.hexdigest()
    except Exception:  # noqa: BLE001 — any doubt disables caching
        return None


# ----------------------------------------------------------------------
# the store
# ----------------------------------------------------------------------
class AotCache(Logger):
    """Content-addressed executable store: ``<key>.bin`` (pickled
    ``serialize_executable`` triple + the ids of the devices it was
    compiled for) + ``<key>.sha256`` sidecar +
    ``<key>.json`` metadata per entry, plus an advisory
    ``manifest.json`` rollup.  Thread-safe; writes are atomic
    (tmp + rename) so concurrent processes sharing one directory never
    observe a torn entry."""

    def __init__(self, directory: str,
                 max_bytes: int | None = None) -> None:
        super().__init__()
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.max_bytes = int(
            root.common.engine.get("aot_cache_bytes", DEFAULT_MAX_BYTES)
            if max_bytes is None else max_bytes)
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.corrupt = 0
        self.puts = 0
        #: key → JAX-cache hits seen when the key missed here
        self._missed_at: dict[str, int] = {}
        _count_jax_cache_hits()

    # -- paths ----------------------------------------------------------
    def _bin(self, key: str) -> str:
        return os.path.join(self.directory, f"{key}.bin")

    def _side(self, key: str) -> str:
        return os.path.join(self.directory, f"{key}.sha256")

    def _meta(self, key: str) -> str:
        return os.path.join(self.directory, f"{key}.json")

    @staticmethod
    def _digest(payload: bytes) -> str:
        return hashlib.sha256(payload).hexdigest()

    @staticmethod
    def _atomic_write(path: str, data: bytes) -> None:
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)

    # -- observability --------------------------------------------------
    def total_bytes(self) -> int:
        total = 0
        try:
            for name in os.listdir(self.directory):
                if name.endswith(".bin"):
                    total += os.path.getsize(
                        os.path.join(self.directory, name))
        except OSError:
            pass
        return total

    def entries(self) -> list[tuple[str, dict]]:
        """``(key, meta)`` for every complete entry, oldest first."""
        out = []
        try:
            names = os.listdir(self.directory)
        except OSError:
            return out
        for name in sorted(names):
            if not name.endswith(".json") or name == "manifest.json":
                continue
            key = name[:-len(".json")]
            path = self._bin(key)
            if not os.path.exists(path):
                continue
            try:
                with open(self._meta(key)) as f:
                    meta = json.load(f)
            except (OSError, ValueError):
                meta = {}
            out.append((key, meta))
        out.sort(key=lambda kv: kv[1].get("seq", 0))
        return out

    def _set_bytes_gauge(self) -> None:
        _metrics.aot_cache_bytes().set(float(self.total_bytes()))

    def _write_manifest(self) -> None:
        rollup = {key: meta for key, meta in self.entries()}
        try:
            self._atomic_write(
                os.path.join(self.directory, "manifest.json"),
                json.dumps(rollup, indent=1, sort_keys=True).encode())
        except OSError:
            pass  # advisory only — entries are self-describing

    # -- the hot paths --------------------------------------------------
    def get(self, key: str, site: str):
        """The deserialized executable for ``key``, or ``None`` (miss
        or quarantined-corrupt — either way the caller traces)."""
        from znicz_tpu.resilience import faults as _faults
        path = self._bin(key)
        try:
            with open(path, "rb") as f:
                payload = f.read()
            with open(self._side(key)) as f:
                want = f.read().strip()
        except OSError:
            _metrics.aot_cache_events(site, "miss").inc()
            with self._lock:
                self.misses += 1
                self._missed_at[key] = _jax_cache_hits
            return None
        if _faults.fire("aotcache.corrupt", at_site=site) is not None:
            # rot the bytes AFTER the sidecar was written — exactly
            # the on-disk corruption digest verification must catch
            mid = len(payload) // 2
            payload = payload[:mid] + b"\xde\xad\xbe\xef" \
                + payload[mid + 4:]
        if self._digest(payload) != want:
            self._quarantine(key, site, "sha256 mismatch")
            return None
        try:
            import jax
            from jax.experimental import serialize_executable as _se
            ser, in_tree, out_tree, device_ids = pickle.loads(payload)
            # load onto the devices the program was compiled for: left
            # to its default, jax loads over EVERY visible device and
            # a one-chip program then refuses its one-shard operands
            by_id = {d.id: d for d in jax.devices()}
            loaded = _se.deserialize_and_load(
                ser, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
        except Exception as exc:  # noqa: BLE001 — corrupt pickle/exe
            self._quarantine(key, site, f"deserialize failed: {exc}")
            return None
        _metrics.aot_cache_events(site, "hit").inc()
        with self._lock:
            self.hits += 1
        return loaded

    def _quarantine(self, key: str, site: str, reason: str) -> None:
        """A corrupt entry is moved aside (never retried, evidence
        kept) and the site falls back to tracing."""
        self.warning("AOT cache entry %s… quarantined (%s) — falling "
                     "back to tracing", key[:12], reason)
        for path in (self._bin(key), self._side(key), self._meta(key)):
            try:
                os.replace(path, f"{path}.quarantined")
            except OSError:
                pass
        _metrics.aot_cache_events(site, "corrupt").inc()
        _metrics.recoveries("aotcache_fallback").inc()
        from znicz_tpu.observe import recorder as _recorder
        _recorder.record("aotcache_quarantine", key=key[:12],
                         site=site, reason=reason)
        with self._lock:
            self.corrupt += 1
        self._set_bytes_gauge()

    def put(self, key: str, compiled, site: str,
            meta: dict | None = None) -> bool:
        """Serialize + store one compiled executable.  Best-effort: an
        executable this backend cannot serialize just stays uncached
        (the compile already happened — nothing is lost).

        An executable that JAX's own persistent compilation cache
        handed back is never stored: serialized a second time it loads
        but fails at dispatch ("Function … not found", CPU backend,
        jax 0.9.0), which no digest can catch.  Any hit of JAX's cache
        between this key's miss and its put counts as "this compile
        may have been one" — a false alarm only costs the entry."""
        with self._lock:
            missed_at = self._missed_at.pop(key, _jax_cache_hits)
        if missed_at != _jax_cache_hits:
            self.debug("AOT cache: program for site %s came through "
                       "JAX's compilation cache — not stored", site)
            return False
        try:
            from jax.experimental import serialize_executable as _se
            device_ids = [d.id for d in
                          compiled.runtime_executable().local_devices()]
            payload = pickle.dumps(
                _se.serialize(compiled) + (device_ids,))
        except Exception as exc:  # noqa: BLE001 — not serializable
            self.debug("AOT cache: executable for site %s not "
                       "serializable (%s)", site, exc)
            return False
        entry = dict(meta or {})
        entry.update({"site": site, "bytes": len(payload),
                      "sha256": self._digest(payload)})
        try:
            with self._lock:
                entry["seq"] = self.puts = self.puts + 1
            self._atomic_write(self._bin(key), payload)
            self._atomic_write(self._side(key),
                               (entry["sha256"] + "\n").encode())
            self._atomic_write(self._meta(key),
                               json.dumps(entry,
                                          sort_keys=True).encode())
        except OSError as exc:
            self.warning("AOT cache write failed for site %s: %s",
                         site, exc)
            return False
        self._trim()
        self._write_manifest()
        self._set_bytes_gauge()
        return True

    def _trim(self) -> None:
        """Size bound: evict oldest entries (by store sequence, mtime
        as the cross-process tiebreak) until under ``max_bytes``."""
        if self.max_bytes <= 0:
            return
        total = self.total_bytes()
        if total <= self.max_bytes:
            return
        for key, _meta in self.entries():
            if total <= self.max_bytes:
                break
            try:
                size = os.path.getsize(self._bin(key))
            except OSError:
                continue
            for path in (self._bin(key), self._side(key),
                         self._meta(key)):
                try:
                    os.remove(path)
                except OSError:
                    pass
            total -= size
            self.debug("AOT cache: evicted %s… (%d bytes, size bound "
                       "%d)", key[:12], size, self.max_bytes)

    # -- publication pack (the fleet path) ------------------------------
    def matching_entries(self, digest: str) -> list[tuple[str, dict]]:
        """Entries whose metadata records this architecture digest."""
        return [(key, meta) for key, meta in self.entries()
                if meta.get("program_digest") == digest]

    def export_pack(self, path: str, digest: str) -> int:
        """Pack every entry for one architecture digest into an
        ``.npz`` (+ ``.sha256`` sidecar) at ``path``; returns the
        entry count (0 = nothing written)."""
        import io
        entries = self.matching_entries(digest)
        if not entries:
            return 0
        arrays = {}
        meta = {}
        for key, entry in entries:
            try:
                with open(self._bin(key), "rb") as f:
                    arrays[f"e_{key}"] = np.frombuffer(
                        f.read(), dtype=np.uint8)
            except OSError:
                continue
            meta[key] = entry
        if not meta:
            return 0
        arrays["pack_meta"] = np.frombuffer(
            json.dumps({"program_digest": digest, "entries": meta},
                       sort_keys=True).encode(), dtype=np.uint8)
        buf = io.BytesIO()
        np.savez_compressed(buf, **arrays)
        self._atomic_write(path, buf.getvalue())
        from znicz_tpu.utils.snapshotter import _sha256_file
        self._atomic_write(f"{path}.sha256",
                           (_sha256_file(path) + "\n").encode())
        return len(meta)

    def import_pack(self, path: str) -> int:
        """Unpack a verified programs pack into this store (per-entry
        digests re-checked; existing keys kept).  Returns entries
        imported.  Raises on a structurally-corrupt pack — the caller
        quarantines the pack and keeps serving (weights are good)."""
        with np.load(path) as pack:
            meta = json.loads(bytes(pack["pack_meta"]).decode())
            imported = 0
            for key, entry in meta["entries"].items():
                if os.path.exists(self._bin(key)):
                    continue
                payload = bytes(pack[f"e_{key}"])
                if self._digest(payload) != entry.get("sha256"):
                    raise ValueError(
                        f"programs pack {path}: entry {key[:12]}… "
                        f"fails its sha256")
                with self._lock:
                    entry["seq"] = self.puts = self.puts + 1
                self._atomic_write(self._bin(key), payload)
                self._atomic_write(self._side(key),
                                   (entry["sha256"] + "\n").encode())
                self._atomic_write(self._meta(key),
                                   json.dumps(entry,
                                              sort_keys=True).encode())
                imported += 1
        if imported:
            self._trim()
            self._write_manifest()
            self._set_bytes_gauge()
        return imported


def guard_donated(loaded, donate_argnums=()):
    """Make a DESERIALIZED executable safe to dispatch with donation.

    Observed on the CPU PJRT backend when the store landed: a
    deserialized executable that donates a multiply-referenced operand
    mishandled the buffer's ownership — the output that aliases the
    donated input got freed while still live (non-finite garbage
    mid-train, ``double free or corruption`` at teardown), while
    natively-compiled programs were immune.  A 2,000-step CPU probe on
    the installed jax (a loaded region program dispatched unguarded)
    no longer reproduces it, but no chip run has validated native
    aliasing of loaded programs, so the default stays the safe one:
    each donated operand is passed as a fresh single-owner device
    copy, bitwise-identical to the un-guarded dispatch.  A memcpy per
    donated leaf per dispatch — orders of magnitude below the compile
    it replaces, but not free: set ``engine.aot_cache_alias =
    "native"`` to dispatch unguarded where the runtime is known
    good."""
    if not donate_argnums:
        return loaded
    if str(root.common.engine.get("aot_cache_alias",
                                  "copy")) == "native":
        return loaded
    import jax
    import jax.numpy as jnp
    donated = frozenset(donate_argnums)

    def call(*args):
        # donated operands may be pytrees (a decode step donates the
        # whole KV-cache tuple) — re-own every leaf
        return loaded(*[
            jax.tree_util.tree_map(jnp.copy, a) if i in donated else a
            for i, a in enumerate(args)])

    return call


# ----------------------------------------------------------------------
# enablement
# ----------------------------------------------------------------------
def active_cache() -> AotCache | None:
    """The process's active store, resolved fresh on every call (the
    config tree is authoritative; the ``ZNICZ_AOT_CACHE`` environment
    variable is the fallback when the tree carries no decision; config
    ``False`` beats everything — the explicit opt-out).  Instances are
    memoized per directory so hit/miss tallies survive re-resolution.
    ``None`` = disabled: every compile site then behaves exactly as it
    did before this round."""
    cfg = root.common.engine.get("aot_cache", None)
    if cfg is False:
        return None
    path = None
    if isinstance(cfg, str):
        path = cfg
    elif cfg is True:
        path = os.environ.get("ZNICZ_AOT_CACHE") or os.path.join(
            str(root.common.dirs.snapshots), "aot_cache")
    elif cfg is None:
        path = os.environ.get("ZNICZ_AOT_CACHE") or None
    if not path:
        return None
    path = os.path.abspath(path)
    with _lock:
        cache = _caches.get(path)
        if cache is None:
            cache = _caches[path] = AotCache(path)
        return cache


def status() -> dict:
    """The ``stats()``/``web_status`` block: enablement, residency and
    this process's verdict tallies (the same numbers the
    ``znicz_aot_cache_total`` series carries)."""
    cache = active_cache()
    if cache is None:
        return {"enabled": False}
    return {
        "enabled": True,
        "dir": cache.directory,
        "entries": len(cache.entries()),
        "bytes": cache.total_bytes(),
        "hits": cache.hits,
        "misses": cache.misses,
        "corrupt": cache.corrupt,
    }


# ----------------------------------------------------------------------
# publication glue (round-13 sidecar machinery grows a programs pack)
# ----------------------------------------------------------------------
def _pack_path(bundle_path: str) -> str:
    base = bundle_path[:-len(".npz")] \
        if bundle_path.endswith(".npz") else bundle_path
    return f"{base}.programs.npz"


def publish_programs(directory: str, prefix: str, version: int,
                     bundle_path: str) -> int:
    """Publish the active cache's programs for ``bundle_path``'s
    architecture as ``<prefix>_v<version>.programs.npz``.  When the
    local cache holds nothing for this architecture, the previous
    version's pack is carried forward (weights-only refreshes keep
    their programs without the trainer ever compiling serving
    programs).  Returns entries packed (0 = no pack written) —
    best-effort: a publish never fails because programs could not be
    packed."""
    cache = active_cache()
    if cache is None:
        return 0
    try:
        from znicz_tpu.export import read_bundle
        manifest, _params = read_bundle(bundle_path)
        digest = program_digest(manifest)
        pack = _pack_path(bundle_path)
        n = cache.export_pack(pack, digest)
        if n:
            return n
        # carry the previous version's pack forward when its
        # architecture still matches
        prev = os.path.join(
            directory, f"{prefix}_v{version - 1:06d}.programs.npz")
        if version > 1 and os.path.exists(prev):
            with np.load(prev) as old:
                meta = json.loads(bytes(old["pack_meta"]).decode())
            if meta.get("program_digest") == digest:
                with open(prev, "rb") as f:
                    AotCache._atomic_write(pack, f.read())
                with open(f"{prev}.sha256") as f:
                    AotCache._atomic_write(
                        f"{pack}.sha256", f.read().encode())
                return len(meta.get("entries", {}))
    except Exception as exc:  # noqa: BLE001 — packing is best-effort
        import logging
        logging.getLogger("aot_cache").warning(
            "programs pack for v%d not published: %s", version, exc)
    return 0


def import_programs(bundle_path: str) -> int:
    """Import the programs pack published beside ``bundle_path`` into
    the active cache (digest-verified; corrupt packs are rejected with
    the fallback counted — the weights are untouched and still serve).
    Returns entries imported."""
    cache = active_cache()
    pack = _pack_path(bundle_path)
    if cache is None or not os.path.exists(pack):
        return 0
    try:
        from znicz_tpu.utils.snapshotter import (SnapshotCorrupt,
                                                 _sha256_file)
        sidecar = f"{pack}.sha256"
        if not os.path.exists(sidecar):
            raise SnapshotCorrupt(f"{pack}: no sha256 sidecar")
        with open(sidecar) as f:
            want = f.read().strip()
        got = _sha256_file(pack)
        if got != want:
            raise SnapshotCorrupt(
                f"{pack}: sha256 {got[:12]}… != sidecar {want[:12]}…")
        return cache.import_pack(pack)
    except Exception as exc:  # noqa: BLE001 — corrupt pack
        import logging
        logging.getLogger("aot_cache").warning(
            "programs pack rejected (%s) — serving will trace", exc)
        _metrics.snapshot_failures("programs").inc()
        _metrics.aot_cache_events("publish", "corrupt").inc()
        _metrics.recoveries("aotcache_fallback").inc()
        return 0
