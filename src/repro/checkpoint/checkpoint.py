"""Sharded, fault-tolerant checkpointing.

Format: one zstd-compressed msgpack file per host process holding that
host's addressable shard data + a JSON manifest with logical shapes/dtypes
and tree structure.  Properties required at 1000-node scale:

 * atomic: data written to ``step_N.tmp`` then renamed; a ``COMMIT`` marker
   written last — restore only considers committed steps.
 * async: serialization happens on a daemon thread; the train loop only
   blocks on the *previous* save (double-buffer); a failed async save is
   re-raised from ``CheckpointManager.wait()`` / the next ``save_async``
   and emitted as a ``checkpoint_error`` event — it never silently looks
   committed.
 * integrity: the manifest carries a sha256 digest per shard file;
   ``load_checkpoint`` verifies them (plus payload sizes against the
   manifest shapes) and, instead of crashing on a bit-flipped or
   truncated shard, quarantines the bad step (renamed to
   ``quarantine_step_N``, emitted as a ``checkpoint_corrupt`` event) and
   falls back through earlier committed steps (docs/resilience.md).
 * elastic restore: the manifest stores logical arrays, not device layouts;
   ``load_checkpoint`` re-shards onto whatever mesh the restart got
   (tested: save on 8 devices, restore on 4).
 * GC: keep-last-k committed checkpoints (quarantined steps are not GC'd —
   they are the post-mortem evidence).
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import msgpack
import numpy as np

import zstandard

from repro.obs import events as obs_events


class CheckpointError(RuntimeError):
    """Checkpoint/template incompatibility or a failed save — a clear,
    typed error instead of a raw KeyError/frombuffer crash."""


class CheckpointCorruptError(CheckpointError):
    """On-disk damage (digest mismatch, truncated/missing/undecodable
    shard).  ``load_checkpoint`` quarantines the step and falls back."""


def _compress(raw: bytes) -> bytes:
    return zstandard.ZstdCompressor(level=3).compress(raw)


def _decompress(buf: bytes) -> bytes:
    return zstandard.ZstdDecompressor().decompress(buf)

_KEY_SEP = "/"


def _flatten(tree) -> Dict[str, Any]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _KEY_SEP.join(_path_str(p) for p in path)
        flat[key] = leaf
    return flat


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return f"#{p.idx}"
    if hasattr(p, "name"):
        return str(p.name)
    return str(p)


def save_checkpoint(directory: str, step: int, tree, *,
                    extra: Optional[Dict] = None) -> str:
    """Synchronous sharded save (this process's addressable data)."""
    os.makedirs(directory, exist_ok=True)
    tmp = os.path.join(directory, f"step_{step}.tmp")
    final = os.path.join(directory, f"step_{step}")
    if os.path.exists(os.path.join(final, "COMMIT")):
        # idempotent: this step is already committed (e.g. the periodic
        # save and the end-of-run save coincide) — renaming over it would
        # fail with ENOTEMPTY and the data is already durable
        return final
    if os.path.exists(final):
        # crash window leftover: renamed but never committed — restore
        # ignores it, and it would ENOTEMPTY the rename below forever
        shutil.rmtree(final)
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    flat = _flatten(tree)
    manifest = {"step": step, "extra": extra or {}, "arrays": {}}
    payload = {}
    for key, leaf in flat.items():
        if leaf is None:
            manifest["arrays"][key] = {"kind": "none"}
            continue
        arr = np.asarray(jax.device_get(leaf))
        manifest["arrays"][key] = {"kind": "array", "dtype": str(arr.dtype),
                                   "shape": list(arr.shape)}
        payload[key] = (arr.tobytes(), str(arr.dtype), list(arr.shape))
    proc = jax.process_index()
    raw = msgpack.packb(payload, use_bin_type=True)
    shard_name = f"shard_{proc}.msgpack.zst"
    comp = _compress(raw)
    # integrity: digest of the on-disk bytes, verified by load_checkpoint
    manifest["digests"] = {shard_name: hashlib.sha256(comp).hexdigest()}
    with open(os.path.join(tmp, shard_name), "wb") as f:
        f.write(comp)
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    os.rename(tmp, final)
    with open(os.path.join(final, "COMMIT"), "w") as f:
        f.write("ok")
    obs_events.emit("checkpoint_save", step=step, path=final)
    return final


def committed_steps(directory: str):
    if not os.path.isdir(directory):
        return []
    steps = []
    for name in os.listdir(directory):
        if not name.startswith("step_") or name.endswith(".tmp"):
            continue
        try:                     # stray/quarantined dirs are not steps
            s = int(name.split("_", 1)[1])
        except ValueError:
            continue
        if os.path.exists(os.path.join(directory, name, "COMMIT")):
            steps.append(s)
    return sorted(steps)


def _read_manifest(path: str) -> Dict:
    mpath = os.path.join(path, "manifest.json")
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise CheckpointCorruptError(
            f"{path}: unreadable manifest ({e})") from e
    if not isinstance(manifest, dict) or "arrays" not in manifest:
        raise CheckpointCorruptError(f"{path}: malformed manifest")
    return manifest


def _read_payload(path: str, manifest: Dict) -> Dict:
    digests = manifest.get("digests") or {}
    shard_names = sorted(n for n in os.listdir(path)
                         if n.startswith("shard_"))
    for name in digests:
        if name not in shard_names:
            raise CheckpointCorruptError(
                f"{path}: shard {name} named in the manifest digests is "
                f"missing (COMMIT present — partial/deleted shard)")
    if not shard_names:
        raise CheckpointCorruptError(f"{path}: no shard files")
    payload: Dict = {}
    for name in shard_names:
        with open(os.path.join(path, name), "rb") as f:
            comp = f.read()
        want = digests.get(name)
        if want is not None:
            got = hashlib.sha256(comp).hexdigest()
            if got != want:
                raise CheckpointCorruptError(
                    f"{path}: sha256 mismatch for {name} "
                    f"(manifest {want[:12]}…, on disk {got[:12]}…)")
        try:
            raw = _decompress(comp)
            payload.update(msgpack.unpackb(raw, raw=False))
        except Exception as e:
            raise CheckpointCorruptError(
                f"{path}: shard {name} undecodable ({e!r})") from e
    return payload


def _restore_from(path: str, template, shardings) -> Tuple[Any, Dict]:
    """Verified restore of one committed step dir.  Raises
    CheckpointCorruptError for on-disk damage (caller may fall back) and
    CheckpointError for checkpoint/template incompatibility (caller must
    not — an older checkpoint would be equally incompatible)."""
    manifest = _read_manifest(path)
    payload = _read_payload(path, manifest)
    flat_tpl = _flatten(template)
    flat_sh = _flatten(shardings) if shardings is not None else {}
    restored = {}
    for key, tpl in flat_tpl.items():
        info = manifest["arrays"].get(key)
        if info is None:
            raise CheckpointError(
                f"{path}: checkpoint has no entry for template leaf "
                f"{key!r} — template/checkpoint structure mismatch")
        if info["kind"] == "none":
            restored[key] = None
            continue
        if key not in payload:
            raise CheckpointCorruptError(
                f"{path}: manifest lists {key!r} but no shard holds it "
                f"(missing shard data with COMMIT present)")
        buf, dtype, shape = payload[key]
        if (info.get("dtype"), list(info.get("shape", ()))) != \
                (dtype, list(shape)):
            raise CheckpointCorruptError(
                f"{path}: shard entry {key!r} disagrees with the manifest "
                f"({dtype}{list(shape)} vs {info.get('dtype')}"
                f"{info.get('shape')})")
        if hasattr(tpl, "dtype") and hasattr(tpl, "shape"):
            if str(tpl.dtype) != dtype or list(tpl.shape) != list(shape):
                raise CheckpointError(
                    f"{path}: leaf {key!r} is {dtype}{list(shape)} in the "
                    f"checkpoint but {tpl.dtype}{list(tpl.shape)} in the "
                    f"template — config/arch drift between save and "
                    f"restore")
        want_bytes = int(np.dtype(dtype).itemsize * np.prod(shape,
                                                            dtype=np.int64))
        if len(buf) != want_bytes:
            raise CheckpointCorruptError(
                f"{path}: shard entry {key!r} holds {len(buf)} bytes, "
                f"expected {want_bytes} (truncated shard)")
        arr = np.frombuffer(buf, dtype=np.dtype(dtype)).reshape(shape)
        sh = flat_sh.get(key)
        restored[key] = jax.device_put(arr, sh) if sh is not None else arr
    leaves_order = [_KEY_SEP.join(_path_str(p) for p in path_)
                    for path_, _ in
                    jax.tree_util.tree_flatten_with_path(template)[0]]
    tdef = jax.tree_util.tree_structure(template)
    return (jax.tree_util.tree_unflatten(
        tdef, [restored[k] for k in leaves_order]), manifest["extra"])


def quarantine_step(directory: str, step: int, reason: str) -> str:
    """Move a damaged committed step out of restore's (and GC's) sight,
    keeping the bytes for post-mortem.  Emits ``checkpoint_corrupt``."""
    src = os.path.join(directory, f"step_{step}")
    dst = os.path.join(directory, f"quarantine_step_{step}")
    n = 0
    while os.path.exists(dst):
        n += 1
        dst = os.path.join(directory, f"quarantine_step_{step}.{n}")
    os.rename(src, dst)
    obs_events.emit("checkpoint_corrupt", step=step, path=src,
                    quarantined=dst, reason=reason)
    return dst


def load_checkpoint(directory: str, template, *, step: Optional[int] = None,
                    shardings=None, fallback: bool = True):
    """Restore into `template`'s tree structure; re-shard to `shardings`
    (a matching pytree of NamedSharding or None for host arrays).

    Every shard is verified against the manifest sha256 digests (and
    per-entry byte counts).  A corrupt newest step is quarantined
    (``checkpoint_corrupt`` event) and restore falls back to the next
    older committed step, unless ``fallback=False`` or an explicit
    ``step`` was requested — then the corruption raises."""
    steps = committed_steps(directory)
    if not steps:
        raise FileNotFoundError(f"no committed checkpoints in {directory}")
    explicit = step is not None
    candidates = [step] if explicit else list(reversed(steps))
    if explicit and step not in steps:
        raise FileNotFoundError(
            f"step {step} is not a committed checkpoint in {directory} "
            f"(committed: {steps})")
    failures = []
    for s in candidates:
        path = os.path.join(directory, f"step_{s}")
        try:
            tree, extra = _restore_from(path, template, shardings)
        except CheckpointCorruptError as e:
            if explicit or not fallback:
                raise
            quarantine_step(directory, s, str(e))
            failures.append(str(e))
            continue
        obs_events.emit("checkpoint_restore", step=s, path=path)
        return tree, s, extra
    raise CheckpointCorruptError(
        f"every committed checkpoint in {directory} is corrupt "
        f"({len(failures)} quarantined): " + "; ".join(failures))


class CheckpointManager:
    """Async double-buffered saves + keep-last-k GC.

    A save-thread exception is never swallowed: it is captured, emitted
    as a ``checkpoint_error`` event, and re-raised (as CheckpointError)
    from ``wait()`` — which the next ``save_async`` calls first, so the
    train loop finds out no later than one checkpoint interval after the
    failure instead of discovering at restore time that nothing was ever
    durable."""

    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        self._pending: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self._error_step: Optional[int] = None

    def save_async(self, step: int, tree, extra: Optional[Dict] = None):
        self.wait()
        host_tree = jax.tree.map(
            lambda x: np.asarray(jax.device_get(x)) if x is not None else None,
            tree)

        def work():
            try:
                save_checkpoint(self.directory, step, host_tree, extra=extra)
                self._gc()
            except BaseException as e:   # surfaced by wait()
                self._error = e
                self._error_step = step
                obs_events.emit("checkpoint_error", step=step,
                                directory=self.directory, error=repr(e))

        self._pending = threading.Thread(target=work, daemon=True)
        self._pending.start()

    def wait(self):
        if self._pending is not None:
            self._pending.join()
            self._pending = None
        if self._error is not None:
            e, s = self._error, self._error_step
            self._error = self._error_step = None
            raise CheckpointError(
                f"async checkpoint save of step {s} failed: {e!r}") from e

    def _gc(self):
        steps = committed_steps(self.directory)
        for s in steps[:-self.keep]:
            shutil.rmtree(os.path.join(self.directory, f"step_{s}"),
                          ignore_errors=True)

    def latest_step(self) -> Optional[int]:
        steps = committed_steps(self.directory)
        return steps[-1] if steps else None
