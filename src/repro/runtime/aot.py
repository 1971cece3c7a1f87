"""Ahead-of-time compilation: serialized fleet/engine executables + cache.

Every bucket shape of the serving step pays ~0.8-1.9 s of trace+compile on
its first push (the ``fleet.S*.fleet_compile`` rows in BENCH_fleet.json), so
a restarted or autoscaled worker stalls its whole tile set before emitting
its first decision.  This module kills that cold-start tax with two stacked
mechanisms, both exercised by ``benchmarks/bench_coldstart.py``:

* **Serialized XLA executables** (``jax.experimental.serialize_executable``
  over the donation-free export-wrapped program).  ``save_artifact``
  enumerates the executable set of a fleet/engine (every variant x backend
  x bucket x tile shape, including the faulted and adapt steps — the
  producers declare their own set via ``StreamingFleet.aot_entries()`` /
  ``ServingEngine.aot_entries()``), compiles each and ships the PjRt
  executable itself (``entries/*.xlaexec``).  A worker that loads the
  artifact skips BOTH Python tracing and XLA compilation:
  ``AOTArtifact.compile`` unpickles and loads the binary — milliseconds.
* **Serialized StableHLO** (``jax.export``, ``entries/*.jaxexport``).  The
  portable middle tier: when the executable is absent, signature-mismatched
  or unloadable on this backend, the exported program is deserialized and
  recompiled — tracing is still skipped, XLA compile is paid once.
* **Persistent compilation cache.**  ``save_artifact`` also compiles the
  plain-jit form of every entry, so when JAX's persistent compilation cache
  is on (``setup_compilation_cache``, called by the entry points) a
  plain-JIT restart that shares the cache directory finds its executables
  there instead of compiling them.

Artifacts are **versioned**: the manifest records ``artifact_key()`` — the
jax version, the device kind, and a hash of the kernel/serving sources
(``kernel_fingerprint``).  ``load_artifact`` compares that key against the
running environment and returns ``None`` on any mismatch (with a warning),
so consumers fall back to plain JIT instead of running stale executables;
``ckpt/checkpoint.py`` records the same key in its manifest ``aot`` entry,
giving checkpoints a validity pointer to their executables
(``StreamingFleet.from_artifact`` threads it back through here).

Layout of one artifact directory::

    <dir>/manifest.json         {"version", "key", "entries": [...]}
    <dir>/entries/e_00000.xlaexec   pickled PjRt executable + arg/out trees
    <dir>/entries/e_00000.jaxexport serialized StableHLO (jax.export)

A missing directory, an unreadable blob or a stale key falls back to JIT
with a warning — AOT is an optimization, never a correctness dependency (the
AOT and JIT paths are bit-exact; tests/test_aot.py pins this per
variant/backend).  Callers that must not fall back (``chip_smoke.py``) turn
those warnings into errors and check ``warmup``'s counts.
"""

from __future__ import annotations

import contextlib
import glob
import hashlib
import json
import os
import pickle
import time
import warnings
from dataclasses import dataclass
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax import export as jax_export
from jax.experimental import serialize_executable
from jax.experimental.compilation_cache import compilation_cache as _cc

MANIFEST = "manifest.json"
ENTRY_DIR = "entries"
ARTIFACT_VERSION = 1

# the sources whose edits change the serving programs: the kernels, the
# serving layers that assemble them into the jitted step, and the core
# primitives they call.  Anything else (benchmarks, launchers, models/)
# cannot change an executable, so it does not invalidate artifacts.
_FINGERPRINT_SUBDIRS = ("kernels", "serve", "core", "reliability", "runtime")


def _repro_root() -> str:
    import repro

    if getattr(repro, "__file__", None):  # regular package
        return os.path.dirname(os.path.abspath(repro.__file__))
    return os.path.abspath(list(repro.__path__)[0])  # namespace package


def kernel_fingerprint(root: str | None = None) -> str:
    """Digest of the kernel/serving sources that determine the compiled
    programs (sorted relpath + bytes of every ``.py`` under
    ``_FINGERPRINT_SUBDIRS``).  Part of ``artifact_key``: an edited kernel
    invalidates every serialized executable."""
    root = root or _repro_root()
    h = hashlib.sha256()
    for sub in _FINGERPRINT_SUBDIRS:
        pat = os.path.join(root, sub, "**", "*.py")
        for path in sorted(glob.glob(pat, recursive=True)):
            h.update(os.path.relpath(path, root).encode())
            with open(path, "rb") as f:
                h.update(f.read())
    return h.hexdigest()[:16]


def device_kind(device=None) -> str:
    d = device if device is not None else jax.local_devices()[0]
    return f"{d.platform}:{d.device_kind}"


def artifact_key(*, device=None, root: str | None = None) -> dict:
    """The validity key an artifact is pinned to: serialized executables are
    only safe to reuse under the same jax version, on the same device kind,
    with unchanged kernel sources."""
    return {
        "jax": jax.__version__,
        "device": device_kind(device),
        "kernels": kernel_fingerprint(root),
    }


def register_pytree_serialization(cls: type, name: str) -> None:
    """Register a (meta-field-free) dataclass pytree for ``jax.export``
    serialization; idempotent.  Producers call this next to their
    ``register_dataclass`` so their state types can cross the export
    boundary."""
    try:
        jax_export.register_pytree_node_serialization(
            cls,
            serialized_name=name,
            serialize_auxdata=lambda aux: b"",
            deserialize_auxdata=lambda b: (),
        )
    except ValueError:  # already registered (idempotent re-import)
        pass


# ---------------------------------------------------------------------------
# persistent compilation cache plumbing
# ---------------------------------------------------------------------------


def setup_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing else is set here; otherwise the cache goes to the
    fixed ``<checkout>/.jax_cache`` (gitignored) — a fixed path, because the
    path is part of what a later process must find again.  Every executable
    persists (no minimum compile time: the kernels compile in well under
    the default second).  Called by the entry points (``launch/serve.py``,
    ``benchmarks/run.py``, ``chip_smoke.py``), never at import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.dirname(os.path.dirname(_repro_root())),
                            ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


@contextlib.contextmanager
def persistent_cache_off():
    """Compile the enclosed block without JAX's persistent compilation
    cache, wherever it lives, and restore the setting after.  JAX decides
    once per process whether the cache is used, so that decision is reset on
    the way in and out."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    _cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        _cc.reset_cache()


# ---------------------------------------------------------------------------
# artifact build / load
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AOTEntry:
    """One executable to ahead-of-time compile: a jitted callable plus the
    abstract (ShapeDtypeStruct pytree) arguments of ONE input signature.
    ``static`` holds trailing ``static_argnames``-style concrete values the
    jit needs at trace time; they are baked into the exported program, so
    loaders call the compiled entry with ``args`` only.

    ``cache_args``, when set, is a second signature to pre-compile for the
    persistent cache ONLY (not exported): device-PINNED avals hash to a
    different cache key than the portable ``args`` form, and a plain-JIT
    restart that merely shares the cache directory compiles the pinned form
    (its operands are committed to their tile device)."""

    name: str
    fn: Callable
    args: tuple
    static: tuple = ()
    cache_args: tuple | None = None


def _aval_tree(x: Any) -> Any:
    return jax.tree.map(
        lambda a: a
        if isinstance(a, jax.ShapeDtypeStruct) or not hasattr(a, "shape")
        else jax.ShapeDtypeStruct(a.shape, a.dtype),
        x,
    )


def save_artifact(
    path: str,
    entries: Sequence[AOTEntry],
    *,
    key: dict | None = None,
) -> dict:
    """Compile + serialize ``entries`` into a deploy artifact at ``path``.

    For every entry this (1) exports + serializes the lowered StableHLO to
    ``entries/e_<i>.jaxexport``, (2) pickles the compiled PjRt executable of
    the donation-free export-wrapped program to ``entries/e_<i>.xlaexec``
    (the tier the load path prefers: no tracing, no XLA compile), and
    (3) compiles the plain-jit form of the program too, which lands in the
    persistent compilation cache when that is on, for plain-JIT restarts
    that merely share the cache directory.  Returns the manifest dict.

    Entries whose export fails are still compiled and recorded with
    ``"exported": false`` — the load path then JIT-compiles them.
    """
    os.makedirs(os.path.join(path, ENTRY_DIR), exist_ok=True)
    manifest: dict = {
        "version": ARTIFACT_VERSION,
        "key": key or artifact_key(),
        "entries": [],
    }
    names = set()
    for i, e in enumerate(entries):
        if e.name in names:
            raise ValueError(f"duplicate AOT entry name {e.name!r}")
        names.add(e.name)
        rec = {"name": e.name, "file": None, "exported": False}
        t0 = time.perf_counter()
        # plain-jit compile: populates the persistent cache (when on) for
        # workers that JIT with the shared cache dir but never load the blobs
        e.fn.lower(*e.args, *e.static).compile()
        if e.cache_args is not None:
            e.fn.lower(*e.cache_args, *e.static).compile()
        blob = None
        try:
            blob = jax_export.export(e.fn)(*e.args, *e.static).serialize()
        except Exception as ex:  # unexportable program: JIT at load time
            warnings.warn(
                f"AOT entry {e.name!r}: export failed "
                f"({type(ex).__name__}: {ex}); shipping no executable",
                stacklevel=2,
            )
        if blob is not None:
            fname = f"e_{i:05d}.jaxexport"
            with open(os.path.join(path, ENTRY_DIR, fname), "wb") as f:
                f.write(blob)
            rec["file"] = fname
            rec["exported"] = True
            # compiled afresh: an executable served from the persistent
            # cache re-serializes into a blob that loads but fails at run
            # time (XLA:CPU: "Function ... not found")
            with persistent_cache_off():
                compiled = _compile_exported(
                    jax_export.deserialize(blob), e.args)
            # ship the XLA executable itself (the load path then skips
            # XLA entirely).  This is the donation-free export-wrapped
            # form — exactly what the load path would have compiled
            exec_blob = _serialize_executable(e.name, compiled)
            if exec_blob is not None:
                xname = f"e_{i:05d}.xlaexec"
                with open(os.path.join(path, ENTRY_DIR, xname),
                          "wb") as f:
                    f.write(exec_blob)
                rec["executable"] = xname
        rec["compile_s"] = round(time.perf_counter() - t0, 4)
        manifest["entries"].append(rec)
    with open(os.path.join(path, MANIFEST), "w") as f:
        json.dump(manifest, f, indent=2, sort_keys=True)
        f.write("\n")
    return manifest


def _compile_exported(exported, args: tuple):
    """Lower+compile a deserialized export under the active cache config."""
    return jax.jit(exported.call).lower(*_aval_tree(args)).compile()


def _serialize_executable(name: str, compiled) -> bytes | None:
    """Pickle a ``jax.stages.Compiled`` (PjRt executable + arg/out trees);
    None when this backend cannot serialize executables."""
    try:
        blob, in_tree, out_tree = serialize_executable.serialize(compiled)
        return pickle.dumps((blob, in_tree, out_tree))
    except Exception as ex:
        warnings.warn(
            f"AOT entry {name!r}: executable serialization failed "
            f"({type(ex).__name__}: {ex}); shipping StableHLO only",
            stacklevel=2,
        )
        return None


def _signature_matches(compiled, args: tuple) -> bool:
    """Shape/dtype agreement between a loaded executable's baked input
    signature and the avals a caller wants it for."""
    try:
        have = jax.tree_util.tree_leaves(compiled.args_info)
        want = jax.tree_util.tree_leaves(_aval_tree(args))
        return len(have) == len(want) and all(
            tuple(h.shape) == tuple(w.shape)
            and np.dtype(h.dtype) == np.dtype(w.dtype)
            for h, w in zip(have, want)
        )
    except Exception:  # malformed args_info: treat as a miss, not an error
        return False


def accepts(compiled, args: tuple) -> bool:
    """True when the warmed executable ``compiled`` takes ``args`` as they
    stand: same shapes and dtypes, and every committed array already on the
    devices it was compiled for.  Callers check this BEFORE the call and
    take the jitted path on a miss; once the call is made (and its donated
    operands consumed), any error it raises is the caller's to see."""
    if not _signature_matches(compiled, args):
        return False
    want = jax.tree_util.tree_leaves(compiled.input_shardings[0])
    have = jax.tree_util.tree_leaves(args)
    return len(want) == len(have) and all(
        not isinstance(x, jax.Array) or not x.committed
        or x.sharding.device_set == w.device_set
        for x, w in zip(have, want))


class AOTArtifact:
    """A loaded (key-validated) deploy artifact.

    ``compile(name, *args)`` returns the ready-to-call compiled executable
    for one entry — the shipped PjRt executable when one matches (no
    tracing, no XLA compile), else a recompile of the serialized StableHLO
    (no tracing) — or ``None`` when the entry is missing or unloadable
    (callers fall back to JIT).
    """

    def __init__(self, path: str, manifest: dict):
        self.path = path
        self.manifest = manifest
        self._by_name = {e["name"]: e for e in manifest["entries"]}

    @property
    def key(self) -> dict:
        return self.manifest["key"]

    @property
    def names(self) -> list[str]:
        return [e["name"] for e in self.manifest["entries"]]

    def __contains__(self, name: str) -> bool:
        return name in self._by_name

    def load_exported(self, name: str):
        """The deserialized ``jax.export.Exported`` for one entry (None when
        absent/unavailable)."""
        rec = self._by_name.get(name)
        if rec is None or not rec.get("exported"):
            return None
        try:
            with open(os.path.join(self.path, ENTRY_DIR, rec["file"]),
                      "rb") as f:
                return jax_export.deserialize(f.read())
        except Exception as ex:
            warnings.warn(
                f"AOT entry {name!r}: failed to deserialize "
                f"({type(ex).__name__}: {ex}); falling back to JIT",
                stacklevel=2,
            )
            return None

    def load_executable(self, name: str, args: tuple | None = None):
        """The shipped XLA executable for one entry as a ready-to-call
        ``jax.stages.Compiled`` — no tracing, no XLA compile.  None when the
        entry ships no executable, this backend cannot load one, or ``args``
        disagree with the baked input signature (the caller then takes the
        StableHLO-recompile tier)."""
        rec = self._by_name.get(name)
        if rec is None or not rec.get("executable"):
            return None
        try:
            with open(os.path.join(self.path, ENTRY_DIR,
                                   rec["executable"]), "rb") as f:
                blob, in_tree, out_tree = pickle.load(f)
            loaded = serialize_executable.deserialize_and_load(
                blob, in_tree, out_tree)
        except Exception as ex:
            warnings.warn(
                f"AOT entry {name!r}: executable load failed "
                f"({type(ex).__name__}: {ex}); recompiling from StableHLO",
                stacklevel=2,
            )
            return None
        if args is not None and not _signature_matches(loaded, args):
            return None
        return loaded

    def compile(self, name: str, *args):
        """Compiled executable for entry ``name`` at the given abstract
        args, or None (caller JIT-compiles instead).  Prefers the shipped
        XLA executable; recompiles the serialized StableHLO when the
        executable is absent or signature-mismatched."""
        loaded = self.load_executable(name, args)
        if loaded is not None:
            return loaded
        exported = self.load_exported(name)
        if exported is None:
            return None
        try:
            return _compile_exported(exported, args)
        except Exception as ex:
            warnings.warn(
                f"AOT entry {name!r}: compile of deserialized executable "
                f"failed ({type(ex).__name__}: {ex}); falling back to JIT",
                stacklevel=2,
            )
            return None


def stale_fields(saved: dict, current: dict) -> dict:
    """``{field: (saved, current)}`` for every artifact-key field that
    disagrees — empty means the artifact is valid here."""
    return {
        k: (saved.get(k), current[k])
        for k in current
        if saved.get(k) != current[k]
    }


def load_artifact(
    path: str,
    *,
    expected_key: dict | None = None,
) -> AOTArtifact | None:
    """Load + key-validate a deploy artifact; ``None`` (with a warning) on
    any mismatch or unreadable manifest — the graceful-JIT-fallback
    contract."""
    manifest_path = os.path.join(path, MANIFEST)
    try:
        with open(manifest_path) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError) as ex:
        warnings.warn(
            f"AOT artifact {path!r}: unreadable manifest "
            f"({type(ex).__name__}: {ex}); falling back to JIT",
            stacklevel=2,
        )
        return None
    current = expected_key or artifact_key()
    bad = stale_fields(manifest.get("key", {}), current)
    if bad:
        warnings.warn(
            f"AOT artifact {path!r} is stale: "
            + ", ".join(f"{k}: saved {s!r} != current {c!r}"
                        for k, (s, c) in sorted(bad.items()))
            + "; falling back to JIT",
            stacklevel=2,
        )
        return None
    return AOTArtifact(path, manifest)
