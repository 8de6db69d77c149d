"""On-disk compiled-policy artifact cache.

A cold serving start pays trace + lower + backend compile for every batch
bucket; the serialized ``jax.export`` module makes the first two
persistable.  Entries are keyed by a sha256 over the *cache material* — a
plain-JSON dict of everything the compiled bytes depend on:

- checkpoint fingerprint (content checksum of the weights),
- padded obs leaf shapes/dtypes + the batch bucket,
- precision policy name, graph mode and the GAT implementation,
- jax/jaxlib versions and the lowering platform,
- the artifact format version.

Any drift in any of these changes the key, so a stale entry is simply a
miss — it can never be *served*.  The residual failure modes are handled
explicitly and never crash a start:

- **corrupt blob** (truncated write, bit rot): ``jax.export.deserialize``
  raises; the server logs, recompiles and overwrites the entry;
- **corrupt/missing meta sidecar**: treated as a miss (the meta is the
  proof the blob matches the material — without it the blob is untrusted);
- **material mismatch under the same key** (hash collision, hand-edited
  file): treated as a miss.

Writes are atomic (temp + rename) so a killed process can't leave a
half-written blob behind a validating meta.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import tempfile
from typing import Dict, Optional

log = logging.getLogger("gsc_tpu.serve.cache")

# bump when the on-disk layout or the exported calling convention changes
ARTIFACT_FORMAT = 1


def cache_material(*, fingerprint: str, template, batch: int,
                   precision: str, graph_mode: bool,
                   gnn_impl: str = "xla") -> Dict:
    """The canonical key material for one bucket's artifact (plain JSON;
    ``template`` is a :class:`~gsc_tpu.serve.policy.ObsTemplate`).
    ``gnn_impl`` matters: the actor is lowered THROUGH the configured GAT
    implementation, so an artifact compiled under one must never be served
    as a hit under the other."""
    import jax
    import jaxlib

    return {
        "format": ARTIFACT_FORMAT,
        "ckpt_fingerprint": fingerprint,
        "obs_leaf_shapes": [list(s) for s in template.leaf_shapes],
        "obs_leaf_dtypes": list(template.leaf_dtypes),
        "batch": int(batch),
        "precision": precision,
        "graph_mode": bool(graph_mode),
        "gnn_impl": gnn_impl,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": jax.default_backend(),
    }


class ArtifactCache:
    """Directory of ``<key>.stablehlo`` blobs + ``<key>.json`` meta
    sidecars (key = sha256 of the canonical material JSON)."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)
        # keys this process served from or wrote this session — prune()
        # never deletes them, so GC can't evict the entry a live server
        # is (or just started) running on
        self._active: set = set()

    @staticmethod
    def key_of(material: Dict) -> str:
        canon = json.dumps(material, sort_keys=True, separators=(",", ":"))
        return hashlib.sha256(canon.encode()).hexdigest()[:40]

    def paths(self, material: Dict):
        key = self.key_of(material)
        return (os.path.join(self.root, key + ".stablehlo"),
                os.path.join(self.root, key + ".json"))

    def load(self, material: Dict) -> Optional[bytes]:
        """Serialized module bytes on a validated hit, else None (miss,
        unreadable entry, or meta/material mismatch — all logged, none
        raised: the caller's fallback is always a fresh compile)."""
        blob_path, meta_path = self.paths(material)
        if not os.path.exists(blob_path):
            return None
        try:
            with open(meta_path) as f:
                meta = json.load(f)
        except (OSError, ValueError, UnicodeDecodeError) as e:
            log.warning(
                "serve artifact meta unreadable — treating as a miss and "
                "recompiling: path=%s error=%s:%s",
                meta_path, type(e).__name__, e)
            return None
        if not isinstance(meta, dict) or meta.get("material") != material:
            log.warning(
                "serve artifact meta does not describe this material — "
                "treating as a miss: path=%s", meta_path)
            return None
        try:
            with open(blob_path, "rb") as f:
                blob = f.read()
        except OSError as e:
            log.warning("serve artifact unreadable — recompiling: "
                        "path=%s error=%s", blob_path, e)
            return None
        self._active.add(self.key_of(material))
        return blob

    def store(self, material: Dict, blob: bytes) -> str:
        """Atomic write of blob + meta; returns the blob path."""
        blob_path, meta_path = self.paths(material)
        fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, blob_path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        from ..obs.sinks import write_atomic_json
        write_atomic_json(meta_path, {"material": material,
                                      "bytes": len(blob)})
        self._active.add(self.key_of(material))
        return blob_path

    def prune(self, keep_latest: int, protect=()) -> list:
        """GC stale entries: keep the ``keep_latest`` most recently
        written blobs (mtime order), delete the rest — hot-swap
        publishing mints one artifact set per checkpoint fingerprint, so
        a long train-while-serve run would otherwise grow the cache one
        generation per published version.

        Never deletes an entry this process loaded or stored
        (``self._active``) or one in ``protect`` (explicit keys).  A
        half-entry — blob without meta (torn write) or meta without blob
        (a previously interrupted prune) — counts as an entry and is
        collectable like any other.  Deletion order is meta first, then
        blob: a concurrent ``load`` that still sees the blob reads a
        missing meta and treats it as a miss, never a half-valid hit.
        Returns the pruned keys."""
        if keep_latest < 0:
            raise ValueError(f"keep_latest must be >= 0: {keep_latest}")
        protected = self._active | set(protect)
        entries = {}
        for path in os.listdir(self.root):
            key, ext = os.path.splitext(path)
            if ext not in (".stablehlo", ".json"):
                continue
            full = os.path.join(self.root, path)
            try:
                mtime = os.path.getmtime(full)
            except OSError:
                continue   # deleted under us (concurrent prune)
            entries[key] = max(entries.get(key, 0.0), mtime)
        keep = sorted(entries, key=lambda k: entries[k],
                      reverse=True)[:keep_latest]
        pruned = []
        for key in entries:
            if key in keep or key in protected:
                continue
            for suffix in (".json", ".stablehlo"):
                try:
                    os.unlink(os.path.join(self.root, key + suffix))
                except OSError:
                    pass
            pruned.append(key)
        if pruned:
            log.info("artifact cache pruned %d stale entr%s (kept %d)",
                     len(pruned), "y" if len(pruned) == 1 else "ies",
                     len(entries) - len(pruned))
        return pruned
