"""Columnar zero-copy check path (ISSUE 9): vocab-encode parity with the
scalar interner walk (unicode, ``#@:`` separator chars, subject sets,
vocab misses, randomized tuple strings), ColumnBlock semantics (decode
parity with ``RelationTuple.from_json``, concat/slice/take, cache keys,
miss-only re-encode), the worker wire's packed string columns, the
templated response assembly, and handler-level columnar-vs-scalar
verdict/error parity including PR 7's per-item isolation contract.
"""

import json
import os
import pathlib
import random
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from ketotpu.api.types import (
    ErrIncompleteSubject,
    ErrIncompleteTuple,
    ErrNilSubject,
    KetoAPIError,
    RelationTuple,
    SubjectID,
    SubjectSet,
)
from ketotpu.cache import results as cache_results
from ketotpu.driver import Provider, Registry
from ketotpu.engine import columns, vocab as vocab_mod
from ketotpu.server import wire
from ketotpu.server.handlers import CheckHandler

FIXTURES = pathlib.Path(__file__).parent / "fixtures"

# strings that exercise every separator the tuple grammar uses, plus
# unicode beyond the BMP (4-byte utf-8) and an empty relation
TRICKY = [
    "plain",
    "with:colon",
    "with#hash",
    "with@at",
    "a:b#c@d",
    "naïve-café",
    "日本語オブジェクト",
    "emoji-🔑-key",
    "",
    " leading and trailing ",
    "back\\slash and \"quote\"",
]


def _mk_tuple(ns, obj, rel, subject):
    return RelationTuple(namespace=ns, object=obj, relation=rel,
                         subject=subject)


def _tricky_tuples():
    out = []
    for i, s in enumerate(TRICKY):
        subj = (
            SubjectSet(namespace=f"sns{s}", object=f"sob{s}", relation=s)
            if i % 2 else SubjectID(id=f"user{s}")
        )
        out.append(_mk_tuple(f"ns{s}", f"ob{s}", s, subj))
    return out


# ---------------------------------------------------------------------------
# vocabulary encode parity
# ---------------------------------------------------------------------------


class TestVocabEncodeParity:
    def _assert_parity(self, voc, tuples):
        """encode_columns must equal the scalar lookup walk, item by item."""
        ns = [t.namespace for t in tuples]
        obj = [t.object for t in tuples]
        rel = [t.relation for t in tuples]
        suid = [t.subject.unique_id() for t in tuples]
        q_ns, q_obj, q_rel, q_sub = voc.encode_columns(ns, obj, rel, suid)
        for i, t in enumerate(tuples):
            assert q_ns[i] == voc.namespaces.lookup(t.namespace)
            assert q_obj[i] == voc.objects.lookup(t.object)
            assert q_rel[i] == voc.relations.lookup(t.relation)
            assert q_sub[i] == voc.subject_key(t.subject)

    def test_tricky_strings_and_subject_kinds(self):
        voc = vocab_mod.Vocab()
        tuples = _tricky_tuples()
        for t in tuples:
            voc.intern_tuple(t)
        self._assert_parity(voc, tuples)

    def test_vocab_miss_batches(self):
        """A batch where nothing (then only half) is interned: misses are
        -1 in every column, exactly like scalar lookup."""
        voc = vocab_mod.Vocab()
        tuples = _tricky_tuples()
        self._assert_parity(voc, tuples)  # nothing interned: all -1
        q = voc.encode_columns(
            [t.namespace for t in tuples], [t.object for t in tuples],
            [t.relation for t in tuples],
            [t.subject.unique_id() for t in tuples],
        )
        assert all(int(c[0]) == -1 for c in (q[0], q[1], q[3]))
        for t in tuples[::2]:
            voc.intern_tuple(t)
        self._assert_parity(voc, tuples)  # mixed hit/miss

    def test_vectorized_probe_path_with_post_build_interns(self):
        """Above _TABLE_MIN the hashtab probe engages; strings interned
        AFTER the table build must still resolve (dict fallback is the
        authority for post-build entries)."""
        voc = vocab_mod.Vocab()
        n = vocab_mod._TABLE_MIN + 100
        tuples = [
            _mk_tuple(f"n{i % 7}", f"o{i}", f"r{i % 5}",
                      SubjectID(id=f"u{i}"))
            for i in range(n)
        ]
        for t in tuples:
            voc.intern_tuple(t)
        # force a pack, then intern more WITHOUT doubling
        voc.subjects.lookup_many([t.subject.unique_id() for t in tuples])
        assert voc.subjects._base is not None
        late = [_mk_tuple("n0", f"late{i}", "r0",
                          SubjectID(id=f"late-u{i}")) for i in range(16)]
        for t in late:
            voc.intern_tuple(t)
        assert len(voc.subjects) < 2 * voc.subjects._base.n  # no repack yet
        self._assert_parity(voc, tuples + late)

    def test_property_randomized_tuple_strings(self):
        """Seeded property test: random strings over an adversarial
        alphabet (separators, unicode, long runs) keep exact parity on
        both the dict path and the hashtab path."""
        rng = random.Random(0xC01)
        alphabet = "ab:#@ \té日\U0001f511\\\"xyz"

        def rand_s():
            return "".join(
                rng.choice(alphabet) for _ in range(rng.randrange(0, 24))
            )

        voc = vocab_mod.Vocab()
        tuples = []
        for i in range(1500):
            subj = (
                SubjectSet(namespace=rand_s(), object=rand_s(),
                           relation=rand_s())
                if rng.random() < 0.4 else SubjectID(id=rand_s())
            )
            t = _mk_tuple(rand_s(), rand_s(), rand_s(), subj)
            tuples.append(t)
            if rng.random() < 0.8:  # ~20% of rows stay vocab misses
                voc.intern_tuple(t)
        self._assert_parity(voc, tuples)
        # and again through a ColumnBlock encode (the served carrier)
        block = columns.ColumnBlock.from_tuples(tuples)
        q_ns, q_obj, q_rel, q_sub = block.encode_for(voc)
        for i, t in enumerate(tuples):
            assert q_ns[i] == voc.namespaces.lookup(t.namespace)
            assert q_sub[i] == voc.subject_key(t.subject)


# ---------------------------------------------------------------------------
# ColumnBlock semantics
# ---------------------------------------------------------------------------


class TestColumnBlock:
    def test_decode_items_parity_with_from_json(self):
        """decode_items mirrors RelationTuple.from_json(d or {}) slot by
        slot: same parsed tuples, same typed error per bad slot."""
        raw = [
            {"namespace": "n", "object": "o", "relation": "r",
             "subject_id": "u"},
            {"namespace": "n", "object": "o", "relation": "r"},  # nil subj
            {"namespace": "n", "object": "o", "relation": "r",
             "subject_set": {"namespace": "sn", "object": "so"}},
            {"namespace": "n", "object": "o", "relation": "r",
             "subject_set": {"namespace": "sn"}},  # incomplete subject
            {"namespace": "n", "subject_id": "u"},  # incomplete tuple
            None,  # scalar path does from_json(d or {}) => nil subject
            {"namespace": "na:ïve", "object": "a#b", "relation": "",
             "subject_set": {"namespace": "s@n", "object": "o:o",
                             "relation": "r#r"}},
        ]
        block, errs, keep = columns.decode_items(raw)
        for j, i in enumerate(keep):
            assert block[j] == RelationTuple.from_json(raw[i])
        for i in set(range(len(raw))) - set(keep):
            with pytest.raises(KetoAPIError) as scal:
                RelationTuple.from_json(raw[i] or {})
            assert type(errs[i]) is type(scal.value)
            assert str(errs[i]) == str(scal.value)
        assert {1: str(ErrNilSubject()), 3: str(ErrIncompleteSubject()),
                4: str(ErrIncompleteTuple()), 5: str(ErrNilSubject())} == {
                    i: str(e) for i, e in errs.items()}

    def test_tuple_str_and_cache_key_parity(self):
        tuples = _tricky_tuples()
        block = columns.ColumnBlock.from_tuples(tuples)
        for i, t in enumerate(tuples):
            assert block.tuple_str(i) == str(t)
            assert block.cache_key(i, 3) == cache_results.check_key(t, 3)
            assert block.subject(i) == t.subject

    def test_concat_slice_take_roundtrip(self):
        tuples = _tricky_tuples()
        a = columns.ColumnBlock.from_tuples(tuples[:4])
        b = columns.ColumnBlock.from_tuples(tuples[4:])
        merged = columns.ColumnBlock.concat([a, b])
        assert len(merged) == len(tuples)
        assert [merged[i] for i in range(len(merged))] == tuples
        mid = merged.slice(2, 7)
        assert [mid[i] for i in range(len(mid))] == tuples[2:7]
        picked = merged.take([0, 5, 9])
        assert [picked[i] for i in range(3)] == [
            tuples[0], tuples[5], tuples[9]]

    def test_encode_for_refreshes_only_misses(self):
        """Second encode against the SAME vocab resolves strings interned
        in between (write visibility) without a full re-encode."""
        voc = vocab_mod.Vocab()
        tuples = _tricky_tuples()
        for t in tuples[:5]:
            voc.intern_tuple(t)
        block = columns.ColumnBlock.from_tuples(tuples)
        q1 = block.encode_for(voc)
        assert int(q1[0][7]) == -1  # row 7 not interned yet
        first_enc = block._enc
        for t in tuples[5:]:
            voc.intern_tuple(t)
        q2 = block.encode_for(voc)
        assert block._enc is first_enc  # refreshed in place, not rebuilt
        assert int(q2[0][7]) == voc.namespaces.lookup(tuples[7].namespace)
        assert all(len(m) == 0 for m in block._miss)


# ---------------------------------------------------------------------------
# worker wire string columns
# ---------------------------------------------------------------------------


class TestWireStringColumns:
    def test_pack_unpack_roundtrip(self):
        col = TRICKY + ["", "", "tail"]
        arrays = {}
        wire.pack_strcol(arrays, "ns", col)
        # survive an actual frame pack/unpack cycle
        manifest, payload = wire.pack_arrays(arrays)
        back = wire.unpack_arrays(manifest, payload)
        assert wire.unpack_strcol(back, "ns") == col

    def test_empty_column(self):
        arrays = {}
        wire.pack_strcol(arrays, "ns", [])
        assert wire.unpack_strcol(arrays, "ns") == []

    def test_malformed_offsets_raise_wire_error(self):
        arrays = {}
        wire.pack_strcol(arrays, "ns", ["ab", "cd"])
        bad = dict(arrays)
        bad["ns_o"] = np.array([0, 3, 1], dtype=np.int32)  # negative diff
        with pytest.raises(wire.WireError):
            wire.unpack_strcol(bad, "ns")
        with pytest.raises(wire.WireError):
            wire.unpack_strcol({"ns_b": arrays["ns_b"]}, "ns")


# ---------------------------------------------------------------------------
# response assembly
# ---------------------------------------------------------------------------


class TestResponseAssembly:
    def test_render_matches_scalar_json(self):
        verdicts = np.array([True, False, True, False, False])
        frags = columns.verdict_fragments(verdicts)
        frags[2] = columns.error_fragment("boom ü", 400)
        body = columns.render_batch_body(frags, "MDE=")
        doc = json.loads(body)
        assert doc == {
            "results": [
                {"allowed": True}, {"allowed": False},
                {"error": "boom ü", "status": 400},
                {"allowed": False}, {"allowed": False},
            ],
            "snaptoken": "MDE=",
        }


# ---------------------------------------------------------------------------
# handler-level columnar vs scalar parity (full registry, real engine)
# ---------------------------------------------------------------------------

TUPLES = [
    "Group:dev#members@bob",
    "Group:admin#members@alice",
    "Folder:keto#viewers@Group:dev#members",
    "File:keto/README.md#parents@Folder:keto",
]


@pytest.fixture(scope="module")
def reg():
    cfg = {
        "serve": {
            n: {"host": "127.0.0.1", "port": 0}
            for n in ("read", "write", "metrics", "opl")
        },
        "namespaces": {
            "location": str(FIXTURES / "rewrites_namespaces.keto.ts")
        },
        "engine": {
            "kind": "tpu", "frontier": 1024, "arena": 4096,
            "max_batch": 256, "coalesce_ms": 2,
            "mesh_devices": 0, "mesh_axis": "shard",
        },
        "log": {"request_log": False},
    }
    r = Registry(Provider(cfg)).init()
    r.store().write_relation_tuples(
        *[RelationTuple.from_string(s) for s in TUPLES]
    )
    return r


RAW_MIX = [
    {"namespace": "Group", "object": "dev", "relation": "members",
     "subject_id": "bob"},                                   # allowed
    {"namespace": "File", "object": "keto/README.md", "relation": "view",
     "subject_id": "bob"},                                   # via rewrite
    {"namespace": "File", "object": "keto/README.md", "relation": "view",
     "subject_id": "eve"},                                   # denied
    {"namespace": "Nope", "object": "x", "relation": "y",
     "subject_id": "z"},                                     # unknown ns
    {"namespace": "Group", "object": "dev", "relation": "members"},  # 400
    {},                                                      # 400
    {"namespace": "Folder", "object": "keto", "relation": "viewers",
     "subject_set": {"namespace": "Group", "object": "dev",
                     "relation": "members"}},                # subject set
    {"namespace": "Group", "object": "dev", "relation": "members",
     "subject_set": {"namespace": "Unknown2"}},              # 400 (subject)
]


def _scalar_results(handler, raw, r):
    items = []
    for d in raw:
        try:
            items.append(RelationTuple.from_json(d or {}))
        except KetoAPIError as e:
            items.append(e)
    return handler.batch_check_items(items, 0, r)


class TestHandlerParity:
    def test_columnar_matches_scalar_including_isolation(self, reg):
        handler = CheckHandler(reg)
        scalar = _scalar_results(handler, RAW_MIX, reg)
        allowed, errors = handler.batch_check_columnar(RAW_MIX, 0, reg)
        assert len(allowed) == len(RAW_MIX)
        for i, want in enumerate(scalar):
            if "error" in want:
                assert i in errors
                msg, status = errors[i]
                assert (msg, status) == (want["error"], want["status"])
            else:
                assert i not in errors
                assert bool(allowed[i]) == want["allowed"]
        # spot-check the contract directly, not just parity
        assert bool(allowed[0]) and bool(allowed[1]) and bool(allowed[6])
        assert not allowed[2] and not allowed[3]
        assert errors[4][1] == 400 and errors[5][1] == 400
        assert errors[7][1] == 400

    def test_items_columnar_matches_scalar(self, reg):
        handler = CheckHandler(reg)
        items = []
        for d in RAW_MIX:
            try:
                items.append(RelationTuple.from_json(d or {}))
            except KetoAPIError as e:
                items.append(e)
        scalar = handler.batch_check_items(items, 0, reg)
        allowed, errors = handler.batch_check_items_columnar(items, 0, reg)
        for i, want in enumerate(scalar):
            if "error" in want:
                assert errors[i] == (want["error"], want["status"])
            else:
                assert bool(allowed[i]) == want["allowed"]

    def test_columnar_metrics_vocabulary(self, reg):
        handler = CheckHandler(reg)
        handler.batch_check_columnar(RAW_MIX, 0, reg)
        text = reg.metrics().exposition()
        assert "keto_columnar_batches_total" in text


# ---------------------------------------------------------------------------
# slow e2e: columnar default through `serve --workers 2`
# ---------------------------------------------------------------------------


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post_json(url, payload, timeout=300.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


@pytest.mark.slow
def test_columnar_worker_topology_parity_with_scalar(tmp_path):
    """CI serve-columnar gate: a 4096-item batch through a real
    ``serve --workers 2`` topology on the columnar default path, verdict
    parity item-for-item against the scalar batch endpoint
    (``/relation-tuples/check/batch`` runs batch_check_core, which
    parses and dispatches per item), plus the per-item error-isolation
    contract on a mixed batch."""
    db = tmp_path / "colserve.db"
    seed = Registry(Provider({"dsn": f"sqlite://{db}"}))
    seed.store().migrate_up()
    seed.store().write_relation_tuples(
        *[RelationTuple.from_string(s) for s in TUPLES]
    )
    ports = {n: _free_port() for n in ("read", "write", "metrics", "opl")}
    cfg_path = tmp_path / "colserve.json"
    cfg_path.write_text(json.dumps({
        "dsn": f"sqlite://{db}",
        "serve": {
            n: {"host": "127.0.0.1", "port": p} for n, p in ports.items()
        },
        "namespaces": {
            "location": str(FIXTURES / "rewrites_namespaces.keto.ts")
        },
        "engine": {"kind": "tpu", "frontier": 2048, "arena": 8192,
                   "max_batch": 1024, "mesh_devices": 0,
                   "mesh_axis": "shard"},
        "log": {"request_log": False},
    }))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "ketotpu.cli", "serve",
         "-c", str(cfg_path), "--workers", "2"],
        env=env, cwd=str(pathlib.Path(__file__).parent.parent),
    )
    read = f"http://127.0.0.1:{ports['read']}"
    metrics = f"http://127.0.0.1:{ports['metrics']}"
    try:
        ready_by = time.monotonic() + 180.0
        while True:
            assert proc.poll() is None, "serve --workers died during boot"
            try:
                with urllib.request.urlopen(
                    f"{metrics}/health/ready", timeout=2.0
                ) as resp:
                    if resp.status == 200:
                        break
            except OSError:
                pass
            assert time.monotonic() < ready_by, "topology never became ready"
            time.sleep(0.5)

        big = [
            {"namespace": "File", "object": "keto/README.md",
             "relation": "view", "subject_id": f"user{i}"}
            for i in range(4095)
        ] + [{"namespace": "Group", "object": "dev",
              "relation": "members", "subject_id": "bob"}]
        # warm the wide shape, then the acceptance request
        for n in (1024, 4096):
            status, body = _post_json(
                f"{read}/relation-tuples/batch/check", {"tuples": big[:n]}
            )
            assert status == 200, body
        columnar = [
            r["allowed"] for r in json.loads(body)["results"]
        ]
        status, body = _post_json(
            f"{read}/relation-tuples/check/batch", {"tuples": big}
        )
        assert status == 200, body
        scalar = [r["allowed"] for r in json.loads(body)["results"]]
        assert len(columnar) == 4096
        assert columnar == scalar, "columnar/scalar verdict divergence"
        assert columnar[-1] is True and not any(columnar[:-1])

        # per-item isolation through the worker topology: bad slots fail
        # alone, unknown namespaces deny, neighbours still answer
        status, body = _post_json(
            f"{read}/relation-tuples/batch/check", {"tuples": RAW_MIX}
        )
        assert status == 200, body
        res = json.loads(body)["results"]
        assert res[0] == {"allowed": True}
        assert res[2] == {"allowed": False}
        assert res[3] == {"allowed": False}
        assert res[4]["status"] == 400 and res[5]["status"] == 400
        assert res[6] == {"allowed": True}

        with urllib.request.urlopen(
            f"{metrics}/metrics/prometheus", timeout=30
        ) as resp:
            text = resp.read().decode()
        assert "keto_columnar_batches_total" in text
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)
