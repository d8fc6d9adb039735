"""Fault-tolerant, codec-based checkpointing (no external deps).

Design for 1000+ nodes:
  * step-atomic: write to ``step_<N>.tmp/`` then a single directory rename
    (rename is atomic on POSIX); readers never observe partial state.
  * content-integrity: every array file carries a sha256 in the manifest --
    a corrupted/truncated checkpoint is detected and ``restore_latest``
    falls back to the newest intact one (node-failure recovery).
  * mesh-agnostic: arrays are stored unsharded by path; ``restore`` fills a
    template pytree (from eval_shape) and can device_put onto ANY mesh =>
    elastic re-scale across restarts (128 -> 512 chips or back).
  * retention: keep the newest ``keep`` checkpoints.

Codec layer (manifest v2): leaves that are not plain arrays serialize
through a registered ``LeafCodec``.  The built-in ``qtensor`` codec makes
packed quantized weights first-class on disk -- a QTensor leaf becomes its
packed payload + scale table + scalar exponent (one sha256-checked .npy per
payload) plus static metadata (bits/group_size/shape/format tag) in the
manifest.  Payload shapes are format-specific projections of the logical
(K, N) -- ternary packs K/16 uint32 rows, int4/nf4 K/8, int8/mx store K raw
int8 rows, and mx scale tables have one row per 32-element block -- but the
codec never interprets them: each payload records its own shape/dtype and
the format tag tells the decode side which registry entry owns the bytes,
so new formats round-trip with no codec changes.  A checkpoint can also carry a compiled ``QuantPlan``: ``save``
writes ``quant_plan.json`` next to the arrays and records its sha256 under
the manifest's ``quant_plan`` section; ``_verify`` validates it like any
payload, so a truncated plan can never restore as "unquantized".

Because codec metadata is self-describing, a v2 checkpoint restores without
a template (``restore_tree``) -- this is what lets a serving process
cold-start from a packed artifact with no fp32 params and no model init
(see ``repro.quant.api.save_artifact`` / ``load_artifact``).

Sharded payloads (manifest-v2 shard layout)
-------------------------------------------
``save(..., shardings=...)`` writes any payload whose sharding splits it
into multiple shards as per-shard files instead of one blob.  The on-disk
contract:

  * files: ``<payload>.shard0.npy``, ``<payload>.shard1.npy``, ... -- one
    ``.npy`` per UNIQUE shard of the global array (replicated mesh axes are
    deduplicated: a slice held by several devices is written once).  On a
    multi-host cluster each host writes only its addressable shards into
    the same step directory; here (single host) all shards are addressable
    so one process writes the full set.
  * manifest entry (under ``arrays`` or a codec node's ``arrays``)::

        {"shape": [...], "dtype": "...",
         "shards": [{"file": "<payload>.shard0.npy",
                     "sha256": "...",
                     "index": [[start, stop], ...]},   # one pair per dim
                    ...]}

    replacing the unsharded ``{"file", "sha256", "shape", "dtype"}`` form;
    ``index`` is the shard's slice of the global array, so assembly needs
    no mesh (integrity checks and the template-``restore`` path concatenate
    on the host).  Every shard carries its own sha256 and is verified by
    ``_verify`` like any payload.
  * assembly contract: ``restore_tree(..., shardings=...)`` matches each
    target device's slice (``sharding.devices_indices_map``) against the
    saved shard indices and builds the global array with
    ``jax.make_array_from_single_device_arrays`` -- per-shard files load
    straight onto their owning devices and the global array is never
    materialized on one host.  A layout mismatch (elastic re-scale) falls
    back to host-side concatenation + ``device_put``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import shutil
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantizer import QTensor

PLAN_FILE = "quant_plan.json"


def step_dir(ckpt_dir: str, step: int) -> str:
    return os.path.join(ckpt_dir, f"step_{step:09d}")


# ---------------------------------------------------------------------------
# Leaf codecs: pluggable serialization for non-plain-array leaves.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class LeafCodec:
    """One registered leaf encoding.

    ``matches(leaf)`` decides whether this codec owns a leaf; ``encode``
    splits it into named array payloads (each stored as its own
    sha256-checked file) plus JSON-safe static metadata; ``decode`` is the
    exact inverse.  ``template`` (optional) rebuilds the leaf from
    ShapeDtypeStruct fields + metadata without touching payload bytes --
    what lets ``tree_shapes`` describe a checkpoint abstractly so sharding
    rules can run before any array is read.
    """

    name: str
    matches: Callable[[Any], bool]
    encode: Callable[[Any], Tuple[Dict[str, np.ndarray], Dict[str, Any]]]
    decode: Callable[[Dict[str, np.ndarray], Dict[str, Any]], Any]
    template: Optional[Callable[[Dict[str, Any], Dict[str, Any]], Any]] = None


_CODECS: Dict[str, LeafCodec] = {}


def register_codec(
    name: str,
    *,
    matches: Callable[[Any], bool],
    encode: Callable,
    decode: Callable,
    template: Optional[Callable] = None,
    overwrite: bool = False,
) -> LeafCodec:
    if name in _CODECS and not overwrite:
        raise ValueError(f"codec {name!r} already registered")
    codec = LeafCodec(name, matches, encode, decode, template)
    _CODECS[name] = codec
    return codec


def get_codec(name: str) -> LeafCodec:
    try:
        return _CODECS[name]
    except KeyError:
        raise KeyError(
            f"unknown leaf codec {name!r}; registered: {sorted(_CODECS)}"
        ) from None


def _codec_for(leaf: Any) -> Optional[LeafCodec]:
    for codec in _CODECS.values():
        if codec.matches(leaf):
            return codec
    return None


def _is_codec_leaf(leaf: Any) -> bool:
    return _codec_for(leaf) is not None


# Built-in: packed quantized weights.  (QTensor is the base-layer container
# from repro.core.quantizer; no higher quant layers are imported here.)
def _qt_encode(qt: QTensor) -> Tuple[Dict[str, np.ndarray], Dict[str, Any]]:
    arrays = {
        "packed": np.asarray(qt.packed),
        "scale_m": np.asarray(qt.scale_m),
        "scale_e": np.asarray(qt.scale_e),
    }
    meta = {
        "bits": qt.bits,
        "group_size": qt.group_size,
        "shape": list(qt.shape),
        "fmt": qt.fmt,
    }
    return arrays, meta


def _qt_decode(arrays: Dict[str, np.ndarray], meta: Dict[str, Any]) -> QTensor:
    return QTensor(
        _as_jax(arrays["packed"]),
        _as_jax(arrays["scale_m"]),
        _as_jax(arrays["scale_e"]),
        bits=int(meta["bits"]),
        group_size=int(meta["group_size"]),
        shape=tuple(meta["shape"]),
        fmt=meta.get("fmt", ""),
    )


def _qt_template(fields: Dict[str, Any], meta: Dict[str, Any]) -> QTensor:
    """QTensor over ShapeDtypeStruct fields (no payload bytes read)."""
    return QTensor(
        fields["packed"], fields["scale_m"], fields["scale_e"],
        bits=int(meta["bits"]), group_size=int(meta["group_size"]),
        shape=tuple(meta["shape"]), fmt=meta.get("fmt", ""),
    )


def _as_jax(arr: Any):
    """np payloads -> device arrays; already-assembled jax.Arrays (the
    sharded make_array path) pass through untouched."""
    return arr if isinstance(arr, jax.Array) else jnp.asarray(arr)


register_codec(
    "qtensor",
    matches=lambda leaf: isinstance(leaf, QTensor),
    encode=_qt_encode,
    decode=_qt_decode,
    template=_qt_template,
)


# ---------------------------------------------------------------------------
# Tree <-> path-keyed flat view (codec nodes stay whole).
# ---------------------------------------------------------------------------
def _path_str(path) -> str:
    parts = []
    for e in path:
        if hasattr(e, "key"):
            parts.append(str(e.key))
        elif hasattr(e, "name"):
            parts.append(str(e.name))
        elif hasattr(e, "idx"):
            parts.append(str(e.idx))
    return "/".join(parts)


def _flat_with_paths(tree: Any) -> List[Tuple[str, Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_codec_leaf)
    return [(_path_str(path), leaf) for path, leaf in flat]


def _payload_name(name: str) -> str:
    return hashlib.sha1(name.encode()).hexdigest()[:16] + ".npy"


# ---------------------------------------------------------------------------
# Transient-IO retry.  Payload READS (np.load, sha256 hashing) retry OSError
# with exponential backoff -- a filesystem flake during a serving cold start
# should cost milliseconds, not the boot.  Integrity failures (sha256
# mismatch, malformed manifest) are NOT OSErrors and are never retried:
# corrupt data must fail closed (``_verify`` -> None), because retrying it
# would serve corrupt weights.  ``io_fault_hook`` is the chaos harness's
# injection point (``repro.serving.faults.FlakyIO``).
# ---------------------------------------------------------------------------
IO_RETRIES = 3  # retry attempts AFTER the first failure
IO_BACKOFF_S = 0.05  # first backoff; doubles per retry

_IO_FAULT_HOOK: List[Optional[Callable[[str], None]]] = [None]


def set_io_fault_hook(hook: Optional[Callable[[str], None]]) -> None:
    """Install a callable invoked with every payload path about to be read
    (``None`` uninstalls).  Raising ``OSError`` from it models a transient
    read failure; the retry loop must absorb it."""
    _IO_FAULT_HOOK[0] = hook


@contextlib.contextmanager
def io_fault_hook(hook: Callable[[str], None]):
    """Scoped ``set_io_fault_hook`` -- the hook never outlives the test."""
    set_io_fault_hook(hook)
    try:
        yield hook
    finally:
        set_io_fault_hook(None)


def _read_retry(read: Callable[[str], Any], fpath: str) -> Any:
    """``read(fpath)`` with OSError retry + exponential backoff."""
    delay = IO_BACKOFF_S
    for attempt in range(IO_RETRIES + 1):
        try:
            if _IO_FAULT_HOOK[0] is not None:
                _IO_FAULT_HOOK[0](fpath)
            return read(fpath)
        except OSError:
            if attempt == IO_RETRIES:
                raise
            time.sleep(delay)
            delay *= 2


def _np_load(fpath: str, dtype: str) -> np.ndarray:
    """Load one .npy payload as the manifest's ``dtype``.  ``np.save``
    stores dtypes numpy cannot name (bfloat16) as raw void bytes of the
    same width; they are viewed back here."""
    arr = _read_retry(np.load, fpath)
    return arr.view(np.dtype(dtype)) if arr.dtype.kind == "V" else arr


def _sha256_once(fpath: str) -> str:
    with open(fpath, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _file_sha256(fpath: str) -> str:
    return _read_retry(_sha256_once, fpath)


def _norm_index(idx, shape) -> Tuple[Tuple[int, int], ...]:
    """A devices_indices_map entry -> ((start, stop), ...) per dim."""
    out = []
    for s, dim in zip(idx, shape):
        start = 0 if s.start is None else int(s.start)
        stop = dim if s.stop is None else int(s.stop)
        out.append((start, stop))
    return tuple(out)


def _shard_indices(sharding, shape) -> List[Tuple[Tuple[int, int], ...]]:
    """Unique shard slices of ``shape`` under ``sharding`` (replicated mesh
    axes deduplicated), in first-seen device order."""
    seen: List[Tuple[Tuple[int, int], ...]] = []
    for idx in sharding.devices_indices_map(tuple(shape)).values():
        key = _norm_index(idx, shape)
        if key not in seen:
            seen.append(key)
    return seen


def _write_payload(
    d: str, name: str, arr: np.ndarray, sharding: Any = None
) -> Dict[str, Any]:
    """Write one payload; with a ``sharding`` that splits it, write
    per-shard files (``<payload>.shard{k}.npy``, own sha256 each) instead of
    one blob -- the manifest-v2 shard layout (module docstring)."""
    fname = _payload_name(name)
    indices = (
        _shard_indices(sharding, arr.shape) if sharding is not None else []
    )
    if len(indices) > 1:
        shards = []
        for k, index in enumerate(indices):
            sname = f"{fname[:-len('.npy')]}.shard{k}.npy"
            spath = os.path.join(d, sname)
            np.save(spath, arr[tuple(slice(a, b) for a, b in index)])
            shards.append({
                "file": sname,
                "sha256": _file_sha256(spath),
                "index": [list(p) for p in index],
            })
        return {
            "shards": shards,
            "shape": list(arr.shape),
            "dtype": str(arr.dtype),
        }
    fpath = os.path.join(d, fname)
    np.save(fpath, arr)
    return {
        "file": fname,
        "sha256": _file_sha256(fpath),
        "shape": list(arr.shape),
        "dtype": str(arr.dtype),
    }


def _plan_json(plan: Any) -> Optional[str]:
    if plan is None:
        return None
    return plan if isinstance(plan, str) else plan.to_json()


# ---------------------------------------------------------------------------
# Save.
# ---------------------------------------------------------------------------
def save(
    ckpt_dir: str,
    step: int,
    tree: Any,
    extra: Optional[Dict] = None,
    plan: Any = None,
    shardings: Any = None,
    quant_state: Optional[Dict] = None,
) -> str:
    """Atomically persist ``tree`` at ``step``. Returns the final directory.

    Plain array leaves go to the manifest's ``arrays`` section; leaves owned
    by a registered codec (QTensors) go to ``nodes`` as payload files plus
    static metadata.  ``plan`` (a ``repro.quant.QuantPlan`` or its JSON
    string) is written to ``quant_plan.json`` and checksummed under the
    manifest's ``quant_plan`` section.  ``quant_state`` (a JSON-serializable
    schedule record, e.g. ``repro.quant.QuantState.to_meta()``) rides in the
    manifest's ``quant_state`` section so a mid-schedule TTQ/INQ resume is
    bit-faithful -- the state *arrays* live inside ``tree`` like any other
    leaf.  ``shardings`` (a matching pytree of
    NamedSharding; codec leaves may carry per-field shardings, e.g. a
    QTensor of shardings from ``repro.parallel.qtensor_shardings``) switches
    split payloads to the per-shard layout (module docstring).
    """
    os.makedirs(ckpt_dir, exist_ok=True)
    final = step_dir(ckpt_dir, step)
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    manifest: Dict[str, Any] = {
        "version": 2,
        "step": step,
        "arrays": {},
        "nodes": {},
        "quant_plan": None,
        "quant_state": quant_state,
        "extra": extra or {},
    }
    shard_by_name: Dict[str, Any] = (
        dict(_flat_with_paths(shardings)) if shardings is not None else {}
    )
    for name, leaf in _flat_with_paths(tree):
        codec = _codec_for(leaf)
        sh = shard_by_name.get(name)
        if codec is None:
            manifest["arrays"][name] = _write_payload(
                tmp, name, np.asarray(leaf), sh
            )
        else:
            payloads, meta = codec.encode(leaf)
            manifest["nodes"][name] = {
                "codec": codec.name,
                "meta": meta,
                "arrays": {
                    field: _write_payload(
                        tmp, f"{name}/{field}", arr, getattr(sh, field, None)
                    )
                    for field, arr in payloads.items()
                },
            }
    blob = _plan_json(plan)
    if blob is not None:
        with open(os.path.join(tmp, PLAN_FILE), "w") as f:
            f.write(blob)
        manifest["quant_plan"] = {
            "file": PLAN_FILE,
            "sha256": hashlib.sha256(blob.encode()).hexdigest(),
        }
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)  # atomic publish
    return final


# ---------------------------------------------------------------------------
# Verification (integrity gate for restore_latest's fallback).
# ---------------------------------------------------------------------------
def _shards_tile(meta: Dict[str, Any]) -> bool:
    """Do the shard indices exactly tile the full array?

    Shards written by ``_write_payload`` come from a mesh sharding, so they
    form a regular grid: per dimension, the unique (start, stop) intervals
    must partition [0, dim), and every cross-product cell must be present
    exactly once.  A step directory missing a host's shards (or a
    hand-edited manifest) must FAIL verification -- assembling it would
    leave uninitialized slices in the restored array."""
    shape = meta["shape"]
    boxes = {tuple(tuple(p) for p in s["index"]) for s in meta["shards"]}
    if len(boxes) != len(meta["shards"]):
        return False  # duplicate index -> double-write, reject
    per_dim = []
    for d, dim in enumerate(shape):
        ivals = sorted({box[d] for box in boxes})
        pos = 0
        for start, stop in ivals:
            if start != pos or stop <= start:
                return False
            pos = stop
        if pos != dim:
            return False
        per_dim.append(len(ivals))
    n_cells = 1
    for n in per_dim:
        n_cells *= n
    return len(boxes) == n_cells


def _check_payload(d: str, meta: Dict[str, Any]) -> bool:
    if "shards" in meta:  # sharded payload: tile the array AND verify each
        if not _shards_tile(meta):
            return False
        return all(
            _file_sha256(os.path.join(d, s["file"])) == s["sha256"]
            for s in meta["shards"]
        )
    return _file_sha256(os.path.join(d, meta["file"])) == meta["sha256"]


def _verify(d: str) -> Optional[Dict]:
    """Full-integrity check of one checkpoint directory -> manifest or None.

    Everything the manifest references is validated: array payloads, codec
    node payloads, and the ``quant_plan`` section (checksum AND parseable
    structure -- a truncated plan JSON must fail verification, not restore
    as an unquantized checkpoint)."""
    try:
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        for meta in manifest["arrays"].values():
            if not _check_payload(d, meta):
                return None
        for node in manifest.get("nodes", {}).values():
            if node["codec"] not in _CODECS:
                return None
            for meta in node["arrays"].values():
                if not _check_payload(d, meta):
                    return None
        qp = manifest.get("quant_plan")
        if qp is not None:
            with open(os.path.join(d, qp["file"])) as fh:
                blob = fh.read()
            if hashlib.sha256(blob.encode()).hexdigest() != qp["sha256"]:
                return None
            plan = json.loads(blob)
            if not isinstance(plan, dict) or "sites" not in plan:
                return None
        return manifest
    except (OSError, ValueError, KeyError, TypeError):
        # TypeError: structurally corrupt manifest (e.g. a null array entry)
        # must fall back like any other corruption, not crash restore_latest
        return None


def list_steps(ckpt_dir: str) -> List[int]:
    if not os.path.isdir(ckpt_dir):
        return []
    steps = []
    for name in os.listdir(ckpt_dir):
        if name.startswith("step_") and not name.endswith(".tmp"):
            try:
                steps.append(int(name.split("_")[1]))
            except ValueError:
                pass
    return sorted(steps)


def latest_intact(ckpt_dir: str) -> Tuple[Optional[int], Optional[Dict]]:
    """(step, verified manifest) of the newest intact checkpoint.

    Returning the manifest lets callers thread it into ``restore`` /
    ``restore_tree`` / ``load_plan`` so a large artifact is read-and-hashed
    once per boot, not once per helper."""
    for step in reversed(list_steps(ckpt_dir)):
        manifest = _verify(step_dir(ckpt_dir, step))
        if manifest is not None:
            return step, manifest
    return None, None


def latest_intact_step(ckpt_dir: str) -> Optional[int]:
    """Newest step whose directory passes full verification."""
    return latest_intact(ckpt_dir)[0]


# ---------------------------------------------------------------------------
# Restore.
# ---------------------------------------------------------------------------
def _load_payload(d: str, meta: Dict[str, Any]) -> np.ndarray:
    """Host-side load of one payload; sharded payloads concatenate into a
    single host array (the mesh-free / template-``restore`` path)."""
    if "shards" not in meta:
        return _np_load(os.path.join(d, meta["file"]), meta["dtype"])
    out = np.empty(tuple(meta["shape"]), np.dtype(meta["dtype"]))
    for s in meta["shards"]:
        sl = tuple(slice(a, b) for a, b in s["index"])
        out[sl] = _np_load(os.path.join(d, s["file"]), meta["dtype"])
    return out


def _load_payload_on_mesh(d: str, meta: Dict[str, Any], sharding) -> jax.Array:
    """Assemble one payload directly onto its target sharding.

    When the saved shard indices match the target layout (the common
    save-and-restore-on-the-same-mesh-shape case), each ``.shard{k}`` file
    loads once and is device_put straight onto the devices owning that
    slice -- ``jax.make_array_from_single_device_arrays`` stitches the
    global view and the full array never exists on one host.  An elastic
    layout change falls back to host concatenation + ``device_put``."""
    shape = tuple(meta["shape"])
    if sharding is None:
        return jnp.asarray(_load_payload(d, meta))
    if "shards" in meta:
        saved = {
            tuple(tuple(p) for p in s["index"]): s["file"]
            for s in meta["shards"]
        }
        imap = sharding.devices_indices_map(shape)
        if all(_norm_index(idx, shape) in saved for idx in imap.values()):
            cache: Dict[str, np.ndarray] = {}
            pieces = []
            for dev, idx in imap.items():
                fname = saved[_norm_index(idx, shape)]
                if fname not in cache:
                    cache[fname] = _np_load(
                        os.path.join(d, fname), meta["dtype"]
                    )
                pieces.append(jax.device_put(cache[fname], dev))
            return jax.make_array_from_single_device_arrays(
                shape, sharding, pieces
            )
    return jax.device_put(_load_payload(d, meta), sharding)


def _decode_node(d: str, node: Dict[str, Any], shard_leaf: Any = None) -> Any:
    codec = get_codec(node["codec"])
    if shard_leaf is None:
        arrays = {
            field: _load_payload(d, meta)
            for field, meta in node["arrays"].items()
        }
    else:
        arrays = {
            field: _load_payload_on_mesh(
                d, meta, getattr(shard_leaf, field, None)
            )
            for field, meta in node["arrays"].items()
        }
    return codec.decode(arrays, node["meta"])


def restore(
    ckpt_dir: str, step: int, template: Any, shardings: Any = None,
    manifest: Optional[Dict] = None,
) -> Any:
    """Fill ``template`` (pytree of arrays / ShapeDtypeStructs / QTensors)
    from disk.  ``shardings``: optional matching pytree of NamedSharding for
    elastic placement onto a (possibly different) mesh.  ``manifest``: an
    already-verified manifest (skips re-hashing every payload)."""
    d = step_dir(ckpt_dir, step)
    if manifest is None:
        manifest = _verify(d)
    if manifest is None:
        raise IOError(f"checkpoint {d} missing or corrupt")
    flat_t, treedef = jax.tree_util.tree_flatten_with_path(
        template, is_leaf=_is_codec_leaf
    )
    flat_s = (
        jax.tree_util.tree_flatten(shardings, is_leaf=_is_codec_leaf)[0]
        if shardings is not None
        else [None] * len(flat_t)
    )
    nodes = manifest.get("nodes", {})
    leaves = []
    for (path, leaf), shard in zip(flat_t, flat_s):
        name = _path_str(path)
        if name in nodes:
            val = _decode_node(d, nodes[name])
            leaves.append(jax.device_put(val, shard) if shard is not None else val)
            continue
        meta = manifest["arrays"].get(name)
        if meta is None:
            raise KeyError(f"checkpoint missing array {name!r}")
        arr = _np_load(os.path.join(d, meta["file"]), meta["dtype"])
        if list(arr.shape) != list(leaf.shape):
            raise ValueError(f"{name}: shape {arr.shape} != template {leaf.shape}")
        if shard is not None:
            leaves.append(jax.device_put(arr, shard))
        else:
            leaves.append(jnp.asarray(arr, dtype=leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _insert_by_path(out: Dict[str, Any], name: str, val: Any) -> None:
    node = out
    parts = name.split("/")
    for part in parts[:-1]:
        node = node.setdefault(part, {})
    node[parts[-1]] = val


def restore_tree(
    d: str, manifest: Optional[Dict] = None, shardings: Any = None
) -> Any:
    """Template-free restore of one verified checkpoint directory.

    Rebuilds the nested-dict pytree purely from manifest paths: plain
    arrays load with their stored dtype, codec nodes decode through the
    registry (QTensors come back packed -- the fp32 weights are never
    materialized).  This is the cold-start path for serving from a packed
    artifact.  ``manifest``: an already-verified manifest (skips
    re-hashing).  ``shardings``: a matching pytree of NamedSharding (see
    ``tree_shapes`` for building one without reading payloads) -- sharded
    payloads then assemble per-shard straight onto their owning devices and
    the global tree never materializes on one host."""
    if manifest is None:
        manifest = _verify(d)
    if manifest is None:
        raise IOError(f"checkpoint {d} missing or corrupt")
    shard_by_name: Dict[str, Any] = (
        dict(_flat_with_paths(shardings)) if shardings is not None else {}
    )
    out: Dict[str, Any] = {}
    for name, meta in manifest["arrays"].items():
        sh = shard_by_name.get(name)
        val = (
            _load_payload_on_mesh(d, meta, sh)
            if sh is not None
            else jnp.asarray(_load_payload(d, meta))
        )
        _insert_by_path(out, name, val)
    for name, node in manifest.get("nodes", {}).items():
        _insert_by_path(out, name, _decode_node(d, node, shard_by_name.get(name)))
    return out


def tree_shapes(manifest: Dict[str, Any]) -> Any:
    """Abstract pytree of one checkpoint: ShapeDtypeStructs for plain
    arrays, codec templates (e.g. QTensors over ShapeDtypeStruct fields)
    for codec nodes -- built purely from the manifest, no payload reads.
    This is what sharding rules run against before a mesh-aware restore."""
    out: Dict[str, Any] = {}
    for name, meta in manifest["arrays"].items():
        _insert_by_path(out, name, jax.ShapeDtypeStruct(
            tuple(meta["shape"]), np.dtype(meta["dtype"])
        ))
    for name, node in manifest.get("nodes", {}).items():
        codec = get_codec(node["codec"])
        if codec.template is None:
            raise ValueError(
                f"codec {codec.name!r} has no template builder; cannot "
                "describe this checkpoint abstractly"
            )
        fields = {
            field: jax.ShapeDtypeStruct(tuple(m["shape"]), np.dtype(m["dtype"]))
            for field, m in node["arrays"].items()
        }
        _insert_by_path(out, name, codec.template(fields, node["meta"]))
    return out


def load_plan(d: str, manifest: Optional[Dict] = None):
    """The checkpoint's compiled ``QuantPlan`` (or None if it carries none).

    ``manifest``: an already-verified manifest (skips re-hashing)."""
    if manifest is None:
        manifest = _verify(d)
    if manifest is None:
        raise IOError(f"checkpoint {d} missing or corrupt")
    qp = manifest.get("quant_plan")
    if qp is None:
        return None
    from repro.quant.plan import QuantPlan  # lazy: keep the base layer light

    with open(os.path.join(d, qp["file"])) as f:
        return QuantPlan.from_json(f.read())


def load_quant_state(d: str, manifest: Optional[Dict] = None) -> Optional[Dict]:
    """The checkpoint's quantization-schedule record (``quant_state``
    manifest section; None if it carries none).  Returns the raw meta dict
    -- rebuild with ``repro.quant.QuantState.from_meta``."""
    if manifest is None:
        manifest = _verify(d)
    if manifest is None:
        raise IOError(f"checkpoint {d} missing or corrupt")
    return manifest.get("quant_state")


def load_manifest(d: str) -> Dict[str, Any]:
    """Verified manifest of one checkpoint directory (raises if corrupt)."""
    manifest = _verify(d)
    if manifest is None:
        raise IOError(f"checkpoint {d} missing or corrupt")
    return manifest


def restore_latest(
    ckpt_dir: str, template: Any, shardings: Any = None
) -> Tuple[Optional[int], Any]:
    """Newest intact checkpoint (corruption falls back to older ones)."""
    step, manifest = latest_intact(ckpt_dir)
    if step is None:
        return None, None
    return step, restore(ckpt_dir, step, template, shardings, manifest=manifest)


def dir_bytes(path: str) -> int:
    """Total on-disk size of a checkpoint/artifact directory."""
    return sum(
        os.path.getsize(os.path.join(root, f))
        for root, _, files in os.walk(path)
        for f in files
    )


def retain(ckpt_dir: str, keep: int = 3) -> None:
    steps = list_steps(ckpt_dir)
    for step in steps[:-keep]:
        shutil.rmtree(step_dir(ckpt_dir, step), ignore_errors=True)
