"""Engine bundle: the on-disk format of an AOT-compiled serving engine.

A bundle is a directory:

    <bundle>/
      manifest.json        # fingerprints, geometry, bucket table, digests
      x00000.pdexec        # one serialized XLA executable per artifact
      x00001.pdexec
      xla_cache/           # tier-2: the XLA persistent compilation cache

``manifest.json`` carries everything a loader needs to decide whether
the artifacts are USABLE before touching jax:

- ``fingerprint``: bundle format version + jax/jaxlib versions + the
  backend platform the executables were compiled for. A serialized XLA
  executable is only valid on the jaxlib that produced it — any
  mismatch must reject the whole bundle (counted in
  ``aot.invalidations``), never load-and-hope.
- ``model``: hash of the model class/config and the parameter/buffer
  name+shape+dtype tree. The executables take the weights as arguments,
  so the VALUES may change (a newer checkpoint warm-starts fine), but
  the structure must match exactly.
- ``geometry``: the ContinuousBatchingPredictor constructor arguments
  the programs were compiled against (batch size, page size, max seq
  len, eos/pad ids — eos is baked INTO the decode executable).
- ``buckets``: the shape-bucket table the builder calibrated.
- ``artifacts``: per-executable file name, SHA-256 digest, and the
  program signature it serves. Digests are verified at artifact load;
  a mismatch rejects the bundle (tier-1 never executes corrupt bytes).

Writes go through :mod:`paddle_tpu.framework.integrity` — the same
atomic-write/digest helpers as ``VerifiedCheckpointer`` — so a crash
mid-write never leaves a torn manifest or artifact under its final
name.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import shutil
import threading
import time
from typing import Dict, Optional

from ...framework import integrity as _integrity

__all__ = ["EngineBundle", "BundleInvalid", "runtime_fingerprint",
           "model_fingerprint", "sig_key", "MANIFEST", "FORMAT"]

MANIFEST = "manifest.json"
FORMAT = 1


def _device_ids(compiled):
    """Ids of the devices `compiled` runs on, in its own order."""
    import jax
    shardings = jax.tree_util.tree_leaves(compiled.input_shardings) \
        or jax.tree_util.tree_leaves(compiled.output_shardings)
    return [d.id for d in shardings[0]._device_assignment]


def _load_executable(ser, in_tree, out_tree, device_ids):
    """Deserialize onto the devices the executable was compiled for.
    jax 0.9.0's loader otherwise assumes EVERY device of the backend,
    and a one-device program then demands one shard per device."""
    import jax
    from jax.experimental import serialize_executable as _se
    devices = None
    if device_ids is not None:
        by_id = {d.id: d for d in jax.devices()}
        devices = [by_id[i] for i in device_ids]
    return _se.deserialize_and_load(ser, in_tree, out_tree,
                                    execution_devices=devices)


class BundleInvalid(RuntimeError):
    """The bundle must not be loaded: missing/corrupt manifest, digest
    mismatch, or a fingerprint the current runtime cannot honor. The
    ``reason`` slug feeds the ``aot.invalidations`` counter label."""

    def __init__(self, reason: str, detail: str = ""):
        super().__init__(f"engine bundle invalid ({reason})"
                         + (f": {detail}" if detail else ""))
        self.reason = reason
        self.detail = detail


def runtime_fingerprint() -> Dict:
    """What a serialized executable's validity depends on. Compared
    field-for-field at load: ANY difference rejects the bundle."""
    import jax
    import jaxlib
    return {"format": FORMAT, "jax": jax.__version__,
            "jaxlib": getattr(jaxlib, "__version__", "?"),
            "platform": jax.default_backend()}


def _config_dict(config) -> Dict:
    """Stable, JSON-able view of a model config (dataclass or plain
    object): public scalar/str/bool fields only, sorted."""
    if config is None:
        return {}
    src = getattr(config, "__dict__", None) or {}
    out = {}
    for k in sorted(src):
        if k.startswith("_"):
            continue
        v = src[k]
        if isinstance(v, (int, float, str, bool, type(None))):
            out[k] = v
    return out


def model_fingerprint(model) -> str:
    """SHA-256 over the model's identity: class, config, and the
    parameter/buffer name+shape+dtype tree. Weight VALUES are excluded
    on purpose — the executables take weights as runtime arguments, so
    a newly-trained checkpoint of the same architecture warm-starts
    from the same bundle."""
    spec = {
        "class": type(model).__name__,
        "config": _config_dict(getattr(model, "config", None)),
        "params": [(n, list(p.shape), str(p.dtype))
                   for n, p in model.named_parameters()],
        "buffers": [(n, list(b.shape), str(b.dtype))
                    for n, b in model.named_buffers()],
    }
    return hashlib.sha256(
        json.dumps(spec, sort_keys=True).encode()).hexdigest()


def sig_key(sig) -> str:
    """Stable manifest key for a program signature (nested tuples of
    str/int — the predictor's ``_jit_call`` sig)."""
    return repr(sig)


class EngineBundle:
    """Read/write access to one bundle directory. Thread-safe for
    concurrent ``add_artifact`` write-backs from replica threads."""

    def __init__(self, directory: str):
        self.dir = os.path.abspath(directory)
        self._lock = threading.RLock()
        self._manifest: Optional[Dict] = None

    # ---------------------------------------------------------- paths --
    @property
    def manifest_path(self) -> str:
        return os.path.join(self.dir, MANIFEST)

    @property
    def xla_cache_dir(self) -> str:
        """Tier-2 cache directory (the XLA persistent compilation
        cache lives inside the bundle so both tiers move together)."""
        return os.path.join(self.dir, "xla_cache")

    def exists(self) -> bool:
        return os.path.exists(self.manifest_path)

    # -------------------------------------------------------- manifest --
    def manifest(self, refresh: bool = False) -> Dict:
        with self._lock:
            if self._manifest is None or refresh:
                m = _integrity.read_json(self.manifest_path)
                if m is None:
                    raise BundleInvalid(
                        "manifest", f"unreadable {self.manifest_path}")
                self._manifest = m
            return self._manifest

    def _write_manifest(self, manifest: Dict):
        manifest["updated"] = round(time.time(), 3)
        _integrity.atomic_write_json(self.manifest_path, manifest)
        self._manifest = manifest

    @classmethod
    def create(cls, directory: str, model_hash: str, geometry: Dict,
               buckets: Optional[Dict] = None,
               runtime_config: Optional[Dict] = None) -> "EngineBundle":
        """Initialize (or RESET) a bundle: fresh manifest, stale
        executables removed. This is the 'clean rebuild' entry point —
        an invalidated bundle is re-created, never patched.

        ``runtime_config`` (a ``RuntimeConfig.to_dict()`` payload) is
        recorded verbatim plus its canonical hash: the hash joins the
        bundle identity the same way geometry does — ``warm_start``
        with a different config invalidates, and ``aot_report --verify``
        re-derives the hash from the recorded dict so a hand-edited
        manifest cannot ship a config its hash does not vouch for."""
        b = cls(directory)
        os.makedirs(b.dir, exist_ok=True)
        _integrity.sweep_tmp(b.dir)
        for n in os.listdir(b.dir):
            if n.endswith(".pdexec"):
                try:
                    os.unlink(os.path.join(b.dir, n))
                except OSError:
                    pass
        manifest = {
            "format": FORMAT, "created": round(time.time(), 3),
            "fingerprint": runtime_fingerprint(),
            "model": model_hash, "geometry": dict(geometry),
            "buckets": dict(buckets or {}), "artifacts": {},
        }
        if runtime_config is not None:
            from ...framework.runtime_config import config_hash
            manifest["runtime_config"] = dict(runtime_config)
            manifest["runtime_config_hash"] = config_hash(
                dict(runtime_config))
        b._write_manifest(manifest)
        return b

    # -------------------------------------------------------- validate --
    def validate(self, model_hash: Optional[str] = None) -> Dict:
        """Fingerprint gate: raises :class:`BundleInvalid` unless this
        runtime can execute the bundle's artifacts. Digest checks are
        per-artifact at load (``load_artifact``)."""
        m = self.manifest(refresh=True)
        fp, cur = m.get("fingerprint") or {}, runtime_fingerprint()
        if fp != cur:
            diff = {k: (fp.get(k), cur[k]) for k in cur
                    if fp.get(k) != cur[k]}
            raise BundleInvalid("fingerprint", f"{diff}")
        if model_hash is not None and m.get("model") != model_hash:
            raise BundleInvalid(
                "model", f"bundle {str(m.get('model'))[:12]}... vs "
                f"current {model_hash[:12]}...")
        return m

    # ------------------------------------------------------- artifacts --
    def artifacts(self) -> Dict[str, Dict]:
        try:
            return dict(self.manifest().get("artifacts", {}))
        except BundleInvalid:
            return {}

    def load_artifact(self, key: str):
        """Deserialize one executable → a callable taking the original
        (pre-flatten) argument structure. Digest-verified first: a
        corrupt artifact raises :class:`BundleInvalid` and is never
        handed to the runtime."""
        rec = self.artifacts().get(key)
        if rec is None:
            return None
        path = os.path.join(self.dir, rec["file"])
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError as e:
            raise BundleInvalid("digest", f"missing artifact {key}: {e}")
        if _integrity.sha256_bytes(raw) != rec["sha256"]:
            raise BundleInvalid("digest", f"artifact {key} digest "
                                          "mismatch")
        blob = pickle.loads(raw)
        return _load_executable(blob["ser"], blob["in_tree"],
                                blob["out_tree"], blob.get("device_ids"))

    def add_artifact(self, sig, compiled) -> Dict:
        """Serialize a compiled executable into the bundle (the
        write-back half of bucket-miss fallback) and record it in the
        manifest atomically."""
        from jax.experimental import serialize_executable as _se
        ser, in_tree, out_tree = _se.serialize(compiled)
        device_ids = _device_ids(compiled)
        # round-trip fence BEFORE persisting: some executables (e.g.
        # ones the backend handed back from a persistent-cache hit,
        # still so on jaxlib 0.9.0) serialize into blobs that cannot
        # be loaded again; writing one would poison every future warm
        # start of this signature
        _load_executable(ser, in_tree, out_tree, device_ids)
        raw = pickle.dumps({"sig": sig, "ser": ser, "in_tree": in_tree,
                            "out_tree": out_tree,
                            "device_ids": device_ids}, protocol=4)
        key = sig_key(sig)
        with self._lock:
            # refresh from disk before merging: replicas across
            # PROCESSES share one bundle (the launcher exports the same
            # engine dir to every rank), so another pid's write-backs
            # must be folded in, not clobbered. The artifact file name
            # is a pure function of the signature — concurrent writers
            # of the SAME sig converge on identical content, different
            # sigs can never collide (a counter-derived name could) —
            # and a manifest record lost to a lingering race window is
            # benign: that sig misses once and is re-added.
            m = self.manifest(refresh=True)  # valid bundles only
            arts = m.setdefault("artifacts", {})
            fname = "x" + _integrity.sha256_bytes(
                key.encode())[:16] + ".pdexec"
            digest = _integrity.atomic_write_bytes(
                os.path.join(self.dir, fname), raw)
            arts[key] = {"file": fname, "sha256": digest,
                         "kind": sig[0] if isinstance(sig, tuple)
                         and sig else "?",
                         "bytes": len(raw)}
            self._write_manifest(m)
            return arts[key]

    def set_buckets(self, buckets: Dict):
        with self._lock:
            m = self.manifest()
            m["buckets"] = dict(buckets)
            self._write_manifest(m)

    def set_geometry(self, geometry: Dict):
        with self._lock:
            m = self.manifest()
            m["geometry"] = dict(geometry)
            self._write_manifest(m)

    # ----------------------------------------------------- tier-2 cache --
    def wipe_xla_cache(self):
        shutil.rmtree(self.xla_cache_dir, ignore_errors=True)
