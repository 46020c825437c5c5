"""AOT-compiled executable store: zero-warmup boot for serving replicas.

Every fleet scale-up, failover revival, or restart pays jit warmup per
(entries bucket, pose bucket, warp_impl, quant dtype, mesh shape) render
program — and the compile set is BOUNDED (engine.py docstring), so it is
enumerable offline. This module persists the compiled executables
themselves:

    build (tools/aot_warmstore.py, or any engine's live write-back)
      -> ship (the artifact directory is plain files; rsync/bake it)
      -> boot (`RenderEngine.warmup` loads executables instead of tracing)
      -> GC   (`AOTStore.gc` drops artifacts whose environment fingerprint
               no longer matches; `tools/audit.py`'s aot_staleness pass
               gates on it)

Artifacts are content-addressed: sha256 of the canonical-JSON *program key*
(bucket shapes + engine statics + mesh shape + environment fingerprint)
names the file, so a key change — different jax version, backend, topology,
or render configuration — can never alias a stale executable. Each artifact
is a pickle of `jax.experimental.serialize_executable.serialize` output
plus the key, written atomically, with a JSON sidecar carrying the key
alone so `--check` / GC / reporting never unpickle executable payloads.

The store is purely an ACCELERATOR, never a correctness dependency: every
load failure (missing, corrupt, key mismatch, deserialization error)
returns None and the engine falls back to live jit — then writes the fresh
executable back so the next replica boots warm.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import logging
import os
import pickle
import tempfile
from typing import Any, Dict, List, Optional, Tuple

from mine_tpu import telemetry

_log = logging.getLogger(__name__)

# artifact / sidecar extensions: <digest>.aotx holds the pickled payload,
# <digest>.json holds the key alone (never unpickled for checks or GC)
ARTIFACT_EXT = ".aotx"
SIDECAR_EXT = ".json"

# bumped when the artifact layout changes; part of every program key so a
# layout change invalidates (misses, not crashes) every old artifact
STORE_SCHEMA = "mtpu-aot1"


def env_fingerprint() -> Dict[str, Any]:
    """The environment a compiled executable is only valid in: jax/jaxlib
    versions, backend platform, and device topology. Part of every program
    key, so artifacts from another environment hash to different names and
    simply miss (and `gc` can sweep them by comparing this dict)."""
    import jax
    import jaxlib
    devices = jax.devices()
    return {
        "schema": STORE_SCHEMA,
        "jax": jax.__version__,
        "jaxlib": jaxlib.version.__version__,
        "backend": jax.default_backend(),
        "devices": f"{len(devices)}x{devices[0].device_kind}",
        "processes": jax.process_count(),
    }


@contextlib.contextmanager
def fresh_compile():
    """Compile inside this block for real, never from JAX's persistent
    compile cache. What goes into the store must come from the compiler:
    XLA:CPU (jaxlib 0.9.0) serializes an executable it loaded from that
    cache without its kernel functions, and the artifact then fails at its
    first call in the replica that boots from it ("Function ... not
    found"). The store is the persistence for these programs anyway."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def key_digest(key: Dict[str, Any]) -> str:
    """Content address: sha256 over the canonical (sorted, compact) JSON of
    the program key."""
    blob = json.dumps(key, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()


class AOTStore:
    """Content-addressed directory of serialized compiled executables.

    `load` returns a ready-to-call `Compiled` (invoked with the program's
    DYNAMIC arguments only — static argnames are baked in) or None on any
    miss or failure; `save` serializes and writes atomically. Counters
    (`hits`/`misses`/`load_errors`/`saves`/`save_errors`) mirror into the
    telemetry registry under `serve.aot.*`.
    """

    def __init__(self, root: str):
        if not root:
            raise ValueError("AOTStore needs a directory path")
        self.root = str(root)
        self.hits = 0
        self.misses = 0
        self.load_errors = 0
        self.saves = 0
        self.save_errors = 0
        self._warned = set()

    # ---------------- paths ----------------

    def _paths(self, digest: str) -> Tuple[str, str]:
        return (os.path.join(self.root, digest + ARTIFACT_EXT),
                os.path.join(self.root, digest + SIDECAR_EXT))

    def _warn_once(self, slot: str, msg: str) -> None:
        if slot not in self._warned:
            self._warned.add(slot)
            _log.warning("%s", msg)

    # ---------------- load / save ----------------

    def load(self, key: Dict[str, Any]):
        """Deserialize the executable for `key`, or None (miss or any
        failure — the caller's live-jit fallback is the contract)."""
        digest = key_digest(key)
        art, _ = self._paths(digest)
        if not os.path.exists(art):
            self.misses += 1
            telemetry.counter("serve.aot.misses").inc()
            return None
        try:
            from jax.experimental import serialize_executable as se
            with open(art, "rb") as f:
                blob = pickle.load(f)
            if blob.get("key") != key:
                # digest collision or a hand-edited artifact: treat as a
                # corrupt entry, never hand back a mismatched executable
                raise ValueError("artifact key does not match request key")
            # onto the devices the program was compiled for, in its own
            # order: given none, jax loads onto every device of the backend
            # and a one-device program then wants a shard per device
            import jax
            by_id = {d.id: d for d in jax.devices()}
            exe = se.deserialize_and_load(
                blob["payload"], blob["in_tree"], blob["out_tree"],
                execution_devices=[by_id[i] for i in blob["device_ids"]])
        except Exception as e:  # noqa: BLE001 - any failure means "miss"
            self.load_errors += 1
            telemetry.counter("serve.aot.load_errors").inc()
            self._warn_once(
                "load:" + digest,
                f"AOT store load failed for {digest[:12]}… ({e!r}); "
                f"falling back to live jit")
            return None
        self.hits += 1
        telemetry.counter("serve.aot.hits").inc()
        return exe

    def save(self, key: Dict[str, Any], compiled) -> bool:
        """Serialize `compiled` under `key` (artifact + sidecar, each via
        atomic tmp+rename). Returns False on any failure — a broken store
        must never break serving."""
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = se.serialize(compiled)
            device_ids = [d.id for d in
                          compiled.runtime_executable().local_devices()]
            blob = pickle.dumps({"key": key, "payload": payload,
                                 "in_tree": in_tree, "out_tree": out_tree,
                                 "device_ids": device_ids})
            os.makedirs(self.root, exist_ok=True)
            digest = key_digest(key)
            art, side = self._paths(digest)
            self._atomic_write(art, blob)
            meta = json.dumps({"key": key, "nbytes": len(blob)},
                              sort_keys=True, indent=1)
            self._atomic_write(side, meta.encode("utf-8"))
        except Exception as e:  # noqa: BLE001
            self.save_errors += 1
            telemetry.counter("serve.aot.save_errors").inc()
            self._warn_once("save", f"AOT store save failed ({e!r}); "
                                    f"serving continues without write-back")
            return False
        self.saves += 1
        telemetry.counter("serve.aot.saves").inc()
        return True

    def _atomic_write(self, path: str, data: bytes) -> None:
        fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(data)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def contains(self, key: Dict[str, Any]) -> bool:
        return os.path.exists(self._paths(key_digest(key))[0])

    # ---------------- inventory / GC ----------------

    def entries(self) -> List[Dict[str, Any]]:
        """[{digest, key, nbytes, corrupt}] from sidecars alone (artifacts
        without a readable sidecar are listed as corrupt — check/GC treat
        them as stale)."""
        out: List[Dict[str, Any]] = []
        if not os.path.isdir(self.root):
            return out
        for name in sorted(os.listdir(self.root)):
            if not name.endswith(ARTIFACT_EXT):
                continue
            digest = name[:-len(ARTIFACT_EXT)]
            art, side = self._paths(digest)
            rec = {"digest": digest, "key": None, "corrupt": False,
                   "nbytes": os.path.getsize(art)}
            try:
                with open(side, "r", encoding="utf-8") as f:
                    meta = json.load(f)
                rec["key"] = meta["key"]
                if key_digest(meta["key"]) != digest:
                    rec["corrupt"] = True
            except Exception:  # noqa: BLE001
                rec["corrupt"] = True
            out.append(rec)
        return out

    def stale_entries(self,
                      fingerprint: Optional[Dict[str, Any]] = None
                      ) -> List[Dict[str, Any]]:
        """Entries whose environment fingerprint differs from the current
        one (plus corrupt entries): exactly the set `gc` removes and the
        audit pass fails on."""
        if fingerprint is None:
            fingerprint = env_fingerprint()
        stale = []
        for rec in self.entries():
            if rec["corrupt"] or \
                    (rec["key"] or {}).get("fingerprint") != fingerprint:
                stale.append(rec)
        return stale

    def gc(self, dry_run: bool = False) -> List[str]:
        """Remove stale/corrupt artifacts (and their sidecars); returns the
        removed digests."""
        removed = []
        for rec in self.stale_entries():
            art, side = self._paths(rec["digest"])
            if not dry_run:
                for p in (art, side):
                    try:
                        os.unlink(p)
                    except OSError:
                        pass
            removed.append(rec["digest"])
        return removed

    def stats(self) -> Dict[str, Any]:
        ents = self.entries()
        return {
            "root": self.root,
            "artifacts": len(ents),
            "bytes": sum(e["nbytes"] for e in ents),
            "hits": self.hits, "misses": self.misses,
            "load_errors": self.load_errors,
            "saves": self.saves, "save_errors": self.save_errors,
        }


# ------------------------------------------------------------------ packing

# manifest filename inside a packed artifact; carries the builder's
# fingerprint so a deploy can see at a glance what environment it targets
PACK_MANIFEST = "MANIFEST.json"


def pack_store(root: str, out_path: str) -> Dict[str, Any]:
    """Pack a store directory into ONE deployable tar artifact.

    The archive is FLAT — artifact/sidecar basenames plus a MANIFEST.json
    carrying the store schema, the builder's environment fingerprint and
    the member list — written atomically (tmp + rename) in sorted member
    order so identical stores pack byte-identically. Returns the manifest.
    Only `ARTIFACT_EXT`/`SIDECAR_EXT` files are packed; anything else in
    the directory is someone else's.
    """
    import io
    import tarfile

    store = AOTStore(root)
    names = sorted(
        f for f in os.listdir(store.root)
        if f.endswith(ARTIFACT_EXT) or f.endswith(SIDECAR_EXT))
    manifest = {
        "schema": STORE_SCHEMA,
        "fingerprint": env_fingerprint(),
        "members": names,
        "artifacts": sum(1 for f in names if f.endswith(ARTIFACT_EXT)),
    }
    out_dir = os.path.dirname(os.path.abspath(out_path)) or "."
    fd, tmp = tempfile.mkstemp(dir=out_dir, suffix=".pack.tmp")
    os.close(fd)
    try:
        with tarfile.open(tmp, "w") as tf:
            blob = json.dumps(manifest, sort_keys=True,
                              indent=1).encode("utf-8")
            info = tarfile.TarInfo(PACK_MANIFEST)
            info.size = len(blob)
            tf.addfile(info, io.BytesIO(blob))
            for name in names:
                tf.add(os.path.join(store.root, name), arcname=name)
        os.replace(tmp, out_path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return manifest


def unpack_store(artifact_path: str, root: str) -> Dict[str, Any]:
    """Unpack a packed artifact into a store directory (created if
    missing); returns the manifest. Member names are validated hard —
    flat basenames with the store's extensions only, so a hostile or
    corrupted archive can never write outside `root` — and each file is
    written atomically so a half-unpacked store still just misses."""
    import tarfile

    os.makedirs(root, exist_ok=True)
    manifest: Dict[str, Any] = {}
    with tarfile.open(artifact_path, "r") as tf:
        for m in tf.getmembers():
            name = m.name
            if not m.isfile() or name != os.path.basename(name) \
                    or name.startswith("."):
                raise ValueError(
                    f"packed store member {name!r} is not a flat file")
            if name == PACK_MANIFEST:
                manifest = json.loads(tf.extractfile(m).read())
                continue
            if not (name.endswith(ARTIFACT_EXT)
                    or name.endswith(SIDECAR_EXT)):
                raise ValueError(
                    f"packed store member {name!r} has a foreign extension")
            fd, tmp = tempfile.mkstemp(dir=root, suffix=".unpack.tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(tf.extractfile(m).read())
                os.replace(tmp, os.path.join(root, name))
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
    return manifest
