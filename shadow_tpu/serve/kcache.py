"""AOT window-kernel cache: warm restart means zero recompiles.

Every gear tier, fleet shape, and sync mode compiles its own window
kernel, and every process pays those traces again from scratch — the
r03–r05 bench rounds showed cold-start compiles dominating small sweeps.
This module persists compiled fleet kernels with JAX's AOT export
machinery (`jax.export.export` → StableHLO bytes → `deserialize`), keyed
by everything that shapes the program:

    (kernel-config digest, kernel tag, argument avals, jax/jaxlib
     version, backend platform)

so a restarted daemon (or a rerun bench) re-binds its fleet kernels from
disk without re-tracing a single Python window step — the
`kernel_traces` metric stays 0, which is exactly the gated property the
serve smoke asserts. Determinism is free: the deserialized artifact is
the same StableHLO the live trace produced, and the engine's integer
kernels are exact, so cached and fresh kernels commit bit-identical
event streams (tests/test_serve.py pins this).

Trust nothing on disk: each entry carries a sidecar header with a
sha256 content digest and the producing jax/jaxlib versions. A corrupt,
torn, or version-skewed entry is EVICTED and recompiled — never
deserialized on faith (`evictions` counts them).

The cache root (`cache_root()`: SHADOW_TPU_CACHE_DIR, else `.jax_cache`
next to the repo) holds the AOT entries under `<root>/aot/` and, by
default, XLA's own persistent cache directly under `<root>`, so the
daemon, the bench and the chip smoke warm each other. Where
JAX_COMPILATION_CACHE_DIR is set, XLA's cache lives there instead: that
directory was placed from outside, so nothing here sets another one or
deletes anything in it (`enable_xla_cache`).
"""

from __future__ import annotations

import hashlib
import json
import os

_AOT_SUBDIR = "aot"
HEADER_VERSION = 1


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))


def cache_root() -> str:
    """The shared cache root: SHADOW_TPU_CACHE_DIR when set, else
    `.jax_cache` next to the repo (a fixed path, so its entries hit)."""
    return os.environ.get("SHADOW_TPU_CACHE_DIR") or os.path.join(
        repo_root(), ".jax_cache"
    )


_MACHINE_FP: str | None = None
_MACHINE_MARKER = "machine.json"


def machine_fingerprint() -> str:
    """Digest of the TARGET MACHINE the compiler lowers for: backend
    device kind plus (on CPU backends) the host's CPU feature flags.

    XLA:CPU bakes the compile host's feature set (AVX-512 tiers, AMX…)
    into every artifact; executing an entry compiled on a different
    machine emits the "Machine type used for XLA:CPU compilation doesn't
    match the machine type for execution … could lead to execution
    errors such as SIGILL" warning visible in every MULTICHIP_r0* tail
    when a cache directory travels between hosts. Folding this digest
    into the kcache key (and the XLA cache's machine marker,
    enable_xla_cache) makes a foreign-machine entry a clean miss/evict
    instead of a warning-spewing hazard."""
    global _MACHINE_FP
    if _MACHINE_FP is not None:
        return _MACHINE_FP
    import platform

    # Host-derived only — this must NOT touch jax.devices(): it runs at
    # cache-enable time, which is often BEFORE device virtualization
    # (bench --mesh-smoke forces an 8-device CPU mesh after import), and
    # initializing the backend here would pin the real device set. The
    # accelerator platform already rides the cache key separately
    # (jax.default_backend() at key time); this digest captures the HOST
    # the XLA:CPU code generator targets.
    parts = [platform.machine(), platform.system()]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    parts.append(" ".join(sorted(line.split(":", 1)[1]
                                                 .split())))
                    break
    except OSError:
        pass
    _MACHINE_FP = hashlib.sha256(
        "\n".join(parts).encode()
    ).hexdigest()[:16]
    return _MACHINE_FP


def _sweep_foreign_machine(root: str, fp: str) -> int:
    """Evict XLA persistent-cache entries compiled on a DIFFERENT
    machine: the root carries a machine marker; on mismatch every
    top-level entry (XLA's flat layout) is removed and the marker
    rewritten — a machine change costs one cold compile, never warning
    spam or a SIGILL hazard. AOT entries under aot/ are key-guarded by
    the same fingerprint and evict themselves on read."""
    marker = os.path.join(root, _MACHINE_MARKER)
    try:
        with open(marker) as f:
            recorded = json.load(f).get("machine")
    except (OSError, ValueError):
        recorded = None
    removed = 0
    if recorded is not None and recorded != fp:
        for name in os.listdir(root):
            p = os.path.join(root, name)
            if name == _MACHINE_MARKER or not os.path.isfile(p):
                continue
            try:
                os.unlink(p)
                removed += 1
            except OSError:
                pass
    if recorded != fp:
        tmp = f"{marker}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump({"machine": fp}, f)
            os.replace(tmp, marker)
        except OSError:
            pass
        finally:
            if os.path.exists(tmp):
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
    return removed


def sweep_corrupt_entries(root: str) -> int:
    """Evict unreadable/zero-length XLA persistent-cache entries so a
    torn write from a killed process never makes jax raise mid-run.
    Walks only the top level (XLA's layout) plus our aot/ sidecars;
    returns the number of entries removed."""
    removed = 0
    for d in (root, os.path.join(root, _AOT_SUBDIR)):
        try:
            names = os.listdir(d)
        except OSError:
            continue
        for name in names:
            p = os.path.join(d, name)
            if not os.path.isfile(p):
                continue
            try:
                with open(p, "rb") as f:
                    ok = bool(f.read(4)) or os.path.getsize(p) == 0
                if os.path.getsize(p) == 0:
                    ok = False
            except OSError:
                ok = False
            if not ok:
                try:
                    os.unlink(p)
                    removed += 1
                except OSError:
                    pass
    return removed


def enable_xla_cache(root: str | None = None) -> tuple[str, int]:
    """Turn on JAX's persistent compilation cache — one call shared by
    bench.py, the serve daemon, the tests and the chip smoke, so all warm
    the same cache. Returns (cache_dir, evicted_count).

    JAX_COMPILATION_CACHE_DIR, when set, wins over `root` and
    `cache_root()`: JAX already reads it, it keys its entries by backend
    itself, and it is never swept. Otherwise the cache lives at `root`
    (default `cache_root()`), with foreign-machine and corrupt entries
    evicted first."""
    import jax

    evicted = 0
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        root = placed
    else:
        root = root or cache_root()
        os.makedirs(root, exist_ok=True)
        evicted = _sweep_foreign_machine(root, machine_fingerprint())
        evicted += sweep_corrupt_entries(root)
    jax.config.update("jax_compilation_cache_dir", root)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return root, evicted


def kernel_config_digest(config: dict) -> str:
    """Digest of a job config's KERNEL-SHAPING fields only: the data-
    plane fields a sweep may vary (seeds, stop times, graph values —
    fleet/sweep.py DATA_PATHS) are excluded, so every job of a kernel-
    compatible sweep maps to the same cache key."""
    from shadow_tpu.fleet.sweep import _flatten, _is_data_path

    flat = _flatten(config)
    shaping = {k: flat[k] for k in sorted(flat) if not _is_data_path(k)}
    return hashlib.sha256(
        json.dumps(shaping, sort_keys=True, default=str).encode()
    ).hexdigest()


_SRC_FINGERPRINT: str | None = None


def kernel_source_fingerprint() -> str:
    """Digest of every KERNEL module's source text (the shadowlint
    module map is the authority on what compiles into window programs).
    Folded into every cache key so a daemon restarted across a code
    upgrade can never hit a stale export and silently replay the OLD
    kernel's semantics — a code change is a cache miss, not a hazard."""
    global _SRC_FINGERPRINT
    if _SRC_FINGERPRINT is not None:
        return _SRC_FINGERPRINT
    from shadow_tpu.analysis.linter import classify_module

    root = repo_root()
    h = hashlib.sha256()
    pkg = os.path.join(root, "shadow_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            rel = os.path.relpath(path, root)
            if classify_module(rel) != "kernel":
                continue
            h.update(rel.encode())
            with open(path, "rb") as f:
                h.update(f.read())
    _SRC_FINGERPRINT = h.hexdigest()
    return _SRC_FINGERPRINT


def _avals_signature(args) -> str:
    """shape/dtype signature of the flattened call arguments — part of
    the key, so a hit is guaranteed arg-compatible with the artifact."""
    import jax
    import numpy as np

    parts = []
    for leaf in jax.tree_util.tree_leaves(args):
        a = np.asarray(leaf)
        parts.append(f"{a.dtype}{list(a.shape)}")
    return ";".join(parts)


class KernelCache:
    """Content-addressed store of serialized `jax.export.Exported`
    window kernels under `<root>/aot/`."""

    def __init__(self, root: str | None = None):
        self.root = root or cache_root()
        self.dir = os.path.join(self.root, _AOT_SUBDIR)
        os.makedirs(self.dir, exist_ok=True)
        self.stats_counters = {
            "hits": 0, "misses": 0, "puts": 0, "evictions": 0,
        }

    # -- keys --

    def key(self, config_digest: str, tag: str, args) -> str:
        import jax
        import jaxlib

        ident = json.dumps({
            "config": config_digest,
            "tag": tag,
            "avals": _avals_signature(args),
            "src": kernel_source_fingerprint(),
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "platform": jax.default_backend(),
            # target-machine fingerprint: an XLA:CPU artifact bakes the
            # compile host's feature set; a cache dir that traveled to a
            # different machine must miss cleanly, not SIGILL-hazard
            "machine": machine_fingerprint(),
        }, sort_keys=True)
        return hashlib.sha256(ident.encode()).hexdigest()[:40]

    def _paths(self, key: str) -> tuple[str, str]:
        base = os.path.join(self.dir, f"k-{key}")
        return f"{base}.bin", f"{base}.json"

    # -- store / load --

    def get(self, key: str):
        """The deserialized Exported for `key`, or None (miss). A
        corrupt/torn/version-skewed entry is evicted and reported as a
        miss — the caller recompiles, it never trusts bad bytes."""
        import jax
        import jaxlib
        from jax import export as jax_export

        bin_path, hdr_path = self._paths(key)
        if not (os.path.exists(bin_path) and os.path.exists(hdr_path)):
            self.stats_counters["misses"] += 1
            return None
        try:
            with open(hdr_path) as f:
                hdr = json.load(f)
            blob = open(bin_path, "rb").read()
            if (
                hdr.get("header_version") != HEADER_VERSION
                or hdr.get("sha256") != hashlib.sha256(blob).hexdigest()
                or hdr.get("jax") != jax.__version__
                or hdr.get("jaxlib") != jaxlib.__version__
                or hdr.get("machine", machine_fingerprint())
                != machine_fingerprint()
            ):
                raise ValueError("header mismatch")
            ex = jax_export.deserialize(bytearray(blob))
        except Exception:  # noqa: BLE001 — any bad entry means EVICT
            self._evict(key)
            self.stats_counters["misses"] += 1
            return None
        self.stats_counters["hits"] += 1
        return ex

    def _evict(self, key: str) -> None:
        for p in self._paths(key):
            try:
                os.unlink(p)
            except OSError:
                pass
        self.stats_counters["evictions"] += 1

    def put(self, key: str, exported) -> None:
        """Persist one Exported atomically (tmp + fsync + rename for the
        payload, header last — a crash mid-put leaves at worst a headerless
        payload that `get` treats as a miss)."""
        import jax
        import jaxlib

        blob = bytes(exported.serialize())
        bin_path, hdr_path = self._paths(key)
        for path, data in (
            (bin_path, blob),
            (hdr_path, json.dumps({
                "header_version": HEADER_VERSION,
                "sha256": hashlib.sha256(blob).hexdigest(),
                "jax": jax.__version__,
                "jaxlib": jaxlib.__version__,
                "machine": machine_fingerprint(),
                "platforms": list(exported.platforms),
                "bytes": len(blob),
            }, indent=1).encode()),
        ):
            tmp = f"{path}.tmp.{os.getpid()}"
            try:
                with open(tmp, "wb") as f:
                    f.write(data)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, path)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
        self.stats_counters["puts"] += 1

    def export_and_put(self, key: str, fn, args):
        """Trace `fn` at `args` (the one compile a cold cache pays),
        persist the artifact, and return the Exported."""
        import jax
        from jax import export as jax_export

        exported = jax_export.export(jax.jit(fn))(*args)
        self.put(key, exported)
        return exported

    # -- introspection --

    def entries(self) -> int:
        try:
            return sum(
                1 for n in os.listdir(self.dir)
                if n.startswith("k-") and n.endswith(".bin")
            )
        except OSError:
            return 0

    def stats(self) -> dict:
        d = dict(self.stats_counters)
        d["entries"] = self.entries()
        return d
