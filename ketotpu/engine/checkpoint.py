"""Snapshot checkpointing: persist/restore the projected device graph.

The durable system of record is the tuple store (storage/sqlite.py); this
module checkpoints the *projection* — the CSR snapshot the device consumes
— so a restarting server can skip re-projection when the store hasn't
moved (SURVEY §5.4: "checkpoint = CSR snapshot + delta log; snaptoken
becomes real").  The snaptoken surface reports the store version the
snapshot was built at; a loaded checkpoint is valid exactly when that
version still matches the store.

Format versioning stands in for the reference's schema migrations
(`internal/persistence/sql/migrations/`, SURVEY §2 "snapshot format
versioning"): every structural change to Snapshot/OpTable/FlatTables must
bump ``SNAPSHOT_FORMAT``, and loads refuse mismatched formats with a
typed error instead of deserializing garbage.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Mapping, Optional, Tuple

import numpy as np

from ketotpu.api.types import KetoAPIError
from ketotpu.engine.optable import FlatTables, OpTable
from ketotpu.engine.snapshot import Snapshot
from ketotpu.engine.vocab import Interner, Vocab

#: bump on ANY structural change to the serialized snapshot layout
#: (v2: node/membership hash tables build at SNAPSHOT_PROBE=4 — a v1
#: checkpoint's deeper-bucket tables would silently miss entries under
#: the shallower lookup unroll; v3: err_reach closure table added for
#: the algebra path's short-circuit gate; v4: InvertResult folds into
#: the p_child_neg edge-parity column — a v3 OpTable still has P_NOT
#: nodes the folded interpreters would mis-handle; v5: host-side
#: node_hi/node_lo/mem_node/mem_subj serialize unpadded — a v4
#: checkpoint's padded columns would break the fold path's exact-length
#: merges; v6: the hash tables store ``tag``/``key_b`` and a tag salt in
#: ``meta`` (engine/hashtab.py) — a v5 table has ``key_a``/``key_b`` and
#: no tag column for the lookups to gather; v7: ``ptr`` packs a bucket's
#: split level over its offset and ``meta`` grew the split salt and the
#: build's counts — a v6 table's deeper buckets would be missed by the
#: four rounds every lookup probes now)
SNAPSHOT_FORMAT = 7

_SCALARS = ("num_rels", "n_nodes", "n_edges", "n_tuples", "version")
_ARRAYS = (
    "taint", "err_reach", "node_hi", "node_lo", "row_ptr",
    "edge_ns", "edge_obj", "edge_rel", "edge_node",
    "mem_node", "mem_subj", "mem_row_ptr", "mem_ord_subj",
    "sub_ns", "sub_obj", "sub_rel",
)
_VOCABS = ("namespaces", "objects", "relations", "subjects")


class SnapshotFormatError(KetoAPIError):
    """Checkpoint format/integrity mismatch; rebuild from the store."""

    status_code = 400


def snapshot_to_arrays(
    snap: Snapshot,
    extra: Dict[str, int] = None,
    cursor: Optional[int] = None,
    head: Optional[int] = None,
    store_version: Optional[int] = None,
) -> Dict[str, np.ndarray]:
    """The checkpoint as one flat dict of plain-dtype arrays.  This is the
    single serialized form: ``save_snapshot`` writes it to an .npz and the
    replication wire op ships it verbatim through ``wire.pack_arrays`` to
    a warm-standby follower.  ``cursor``/``head``/``store_version`` stamp
    the changelog position the base snapshot was built at and the store
    (head, version) observed in the same capture window — they let a load
    replay the overlay tail a background-compacting engine had NOT folded
    into the base at save time (additive v5 keys; absent in older files,
    which were head-exact by construction)."""
    data: Dict[str, np.ndarray] = {
        "format": np.int64(SNAPSHOT_FORMAT),
    }
    if cursor is not None:
        data["ckpt_cursor"] = np.int64(cursor)
    if head is not None:
        data["ckpt_head"] = np.int64(head)
    if store_version is not None:
        data["ckpt_store_version"] = np.int64(store_version)
    for k, v in (extra or {}).items():
        data[f"x_{k}"] = np.int64(v)
    for name in _SCALARS:
        data[f"s_{name}"] = np.int64(getattr(snap, name))
    for name in _ARRAYS:
        data[name] = getattr(snap, name)
    for f in dataclasses.fields(OpTable):
        data[f"op_{f.name}"] = getattr(snap.op, f.name)
    for f in dataclasses.fields(FlatTables):
        data[f"fl_{f.name}"] = getattr(snap.flat, f.name)
    for k, v in snap.node_tab.items():
        data[f"nt_{k}"] = v
    for k, v in snap.mem_tab.items():
        data[f"mt_{k}"] = v
    for name in _VOCABS:
        # fixed-width unicode, NOT object dtype: object arrays round-trip
        # through pickle, and a pickle-loading checkpoint would be an
        # arbitrary-code-execution vector for anyone who can write the file
        strings = getattr(snap.vocab, name).strings()
        data[f"v_{name}"] = np.array(strings, dtype=np.str_) \
            if strings else np.zeros(0, dtype="<U1")
    # overlay safety metadata: the relation-level edge pairs present at
    # build time (delta.apply_changes rejects inserts that extend them)
    data["dyn_pairs"] = np.array(
        sorted(snap.dyn_pairs), dtype=np.int64
    ).reshape(-1, 4) if snap.dyn_pairs else np.zeros((0, 4), np.int64)
    return data


def save_snapshot(
    snap: Snapshot,
    path: str,
    extra: Dict[str, int] = None,
    cursor: Optional[int] = None,
    head: Optional[int] = None,
    store_version: Optional[int] = None,
) -> None:
    """One .npz with every array, the vocab string tables, and scalars.
    ``extra`` lets callers stamp environment facts (e.g. the namespace
    config fingerprint) that gate a load's validity."""
    data = snapshot_to_arrays(
        snap, extra=extra, cursor=cursor, head=head,
        store_version=store_version,
    )
    # atomic publish: a crash mid-write must not leave a truncated file at
    # the path the next boot will read
    tmp = f"{path}.tmp"
    with open(tmp, "wb") as f:
        np.savez_compressed(f, **data)
    os.replace(tmp, path)


def _interner_from(strings) -> Interner:
    it = Interner()
    for s in strings:
        it.intern(str(s))
    return it


def snapshot_from_arrays(
    z: Mapping[str, np.ndarray], want_extra: Dict[str, int] = None
) -> Snapshot:
    """Reconstruct a Snapshot from the flat array dict (an open .npz or a
    dict unpacked off the replication wire); raises SnapshotFormatError on
    format mismatch or when a ``want_extra`` stamp differs."""
    files = getattr(z, "files", None)
    if files is None:
        files = list(z.keys())
    if "format" not in files or int(z["format"]) != SNAPSHOT_FORMAT:
        got = int(z["format"]) if "format" in files else None
        raise SnapshotFormatError(
            f"snapshot checkpoint format {got!r} does not match "
            f"supported format {SNAPSHOT_FORMAT}; rebuild from the store"
        )
    for k, want in (want_extra or {}).items():
        have = int(z[f"x_{k}"]) if f"x_{k}" in files else None
        if have != int(want):
            raise SnapshotFormatError(
                f"snapshot checkpoint stamp {k}={have!r} does not match "
                f"the current environment ({int(want)}); rebuild"
            )
    vocab = Vocab()
    for name in _VOCABS:
        setattr(vocab, name, _interner_from(z[f"v_{name}"]))
    op = OpTable(**{
        f.name: z[f"op_{f.name}"] for f in dataclasses.fields(OpTable)
    })
    flat = FlatTables(**{
        f.name: z[f"fl_{f.name}"] for f in dataclasses.fields(FlatTables)
    })
    kw = {name: z[name] for name in _ARRAYS}
    scalars = {name: int(z[f"s_{name}"]) for name in _SCALARS}
    node_tab = {
        k[3:]: z[k] for k in files if k.startswith("nt_")
    }
    mem_tab = {
        k[3:]: z[k] for k in files if k.startswith("mt_")
    }
    dyn_pairs = {tuple(int(x) for x in row) for row in z["dyn_pairs"]}
    snap = Snapshot(
        vocab=vocab, op=op, flat=flat,
        node_tab=node_tab, mem_tab=mem_tab,
        **kw, **scalars,
    )
    snap.dyn_pairs = dyn_pairs
    return snap


def arrays_cursor(
    z: Mapping[str, np.ndarray]
) -> Tuple[Optional[int], Optional[int], Optional[int]]:
    """(cursor, head, store_version) stamps of a serialized checkpoint, or
    Nones when the file predates them (pre-cursor checkpoints are
    head-exact by construction: saves forced a refresh first)."""
    files = getattr(z, "files", None)
    if files is None:
        files = list(z.keys())

    def stamp(key):
        return int(z[key]) if key in files else None

    return (
        stamp("ckpt_cursor"), stamp("ckpt_head"),
        stamp("ckpt_store_version"),
    )


def load_snapshot(path: str, want_extra: Dict[str, int] = None) -> Snapshot:
    """Load a checkpoint; raises SnapshotFormatError on format mismatch or
    when a ``want_extra`` stamp differs from what was saved."""
    with np.load(path) as z:  # no pickle: all arrays are plain dtypes
        return snapshot_from_arrays(z, want_extra)


def load_snapshot_with_cursor(
    path: str, want_extra: Dict[str, int] = None
) -> Tuple[Snapshot, Optional[int], Optional[int], Optional[int]]:
    """Like load_snapshot, plus the (cursor, head, store_version) stamps."""
    with np.load(path) as z:
        snap = snapshot_from_arrays(z, want_extra)
        cursor, head, store_version = arrays_cursor(z)
    return snap, cursor, head, store_version
