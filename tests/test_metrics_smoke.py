"""Metrics smoke: boot the real daemon, fire traffic, scrape the metrics
port, and assert the stage/phase/shard telemetry vocabulary is live.

This is the CI smoke job's test (one file, fast): the acceptance contract
is that a live daemon exposes ``keto_rpc_stage_seconds`` with at least 4
distinct ``stage`` labels, per-shard mesh gauges, and a populated flight
recorder on the debug endpoint.
"""

import json
import re
import urllib.request

import grpc
import pytest

from ketotpu.api.proto_codec import subject_to_proto
from ketotpu.api.types import RelationTuple, SubjectID
from ketotpu.driver import Provider, Registry
from ketotpu.proto import check_service_pb2 as cs
from ketotpu.proto import relation_tuples_pb2 as rts
from ketotpu.proto.services import CheckServiceStub
from ketotpu.server import serve_all

TUPLES = [
    "Group:admin#members@alice",
    "Doc:readme#viewers@Group:admin#members",
]


@pytest.fixture(scope="module")
def server():
    cfg = Provider(
        {
            "serve": {
                n: {"host": "127.0.0.1", "port": 0}
                for n in ("read", "write", "metrics", "opl")
            },
            "namespaces": [{"name": "Group"}, {"name": "Doc"}],
            "engine": {
                "kind": "tpu",
                "frontier": 1024,
                "arena": 4096,
                "max_batch": 256,
                "coalesce_ms": 2,
            },
            "log": {"request_log": False},
        }
    )
    reg = Registry(cfg).init()
    reg.store().write_relation_tuples(
        *[RelationTuple.from_string(s) for s in TUPLES]
    )
    srv = serve_all(reg)
    yield srv
    srv.stop()


@pytest.fixture(scope="module")
def scrape(server):
    read = "http://%s:%d" % tuple(server.addresses["read"])
    metrics = "http://%s:%d" % tuple(server.addresses["metrics"])

    def get(url):
        with urllib.request.urlopen(url, timeout=30) as resp:
            return resp.read().decode()

    # REST checks (hit + miss) — parse/compute/encode stages, and the
    # coalescer decomposition underneath (coalesce_ms=2 is on)
    for subject in ("alice", "mallory"):
        get(
            f"{read}/relation-tuples/check/openapi?namespace=Doc"
            f"&object=readme&relation=view&subject_id={subject}"
        )
    # REST expand — the expand op's stage vector
    get(
        f"{read}/relation-tuples/expand?namespace=Doc&object=readme"
        "&relation=viewers"
    )
    # one gRPC check — the access-log interceptor's duration histogram
    with grpc.insecure_channel(
        "%s:%d" % tuple(server.addresses["read"])
    ) as ch:
        CheckServiceStub(ch).Check(
            cs.CheckRequest(
                tuple=rts.RelationTuple(
                    namespace="Group", object="admin", relation="members",
                    subject=subject_to_proto(SubjectID("alice")),
                )
            )
        )

    def post(url, payload):
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.read().decode()

    # batch front door — keto_batch_requests_total / keto_batch_size
    post(f"{read}/relation-tuples/batch/check", {
        "tuples": [
            {"namespace": "Doc", "object": "readme",
             "relation": "viewers", "subject_id": s}
            for s in ("alice", "mallory")
        ],
    })
    post(f"{read}/relation-tuples/batch/expand", {
        "subjects": [
            {"namespace": "Doc", "object": "readme", "relation": "viewers"},
        ],
    })

    # framed worker wire — one in-process owner round trip so the
    # byte/call counters are live on the same scrape (owner side counts
    # into the daemon registry; the worker side is handed that registry's
    # metrics explicitly)
    import os
    import tempfile

    from ketotpu.server.workers import EngineHostServer, RemoteCheckEngine

    sock = os.path.join(
        tempfile.mkdtemp(prefix="keto-wire-smoke-"), "engine.sock"
    )
    host = EngineHostServer(server.registry, sock).start()
    try:
        remote = RemoteCheckEngine(
            sock, metrics=server.registry.metrics()
        )
        assert remote.batch_check([
            RelationTuple.from_string("Group:admin#members@alice"),
        ]) == [True]
    finally:
        host.stop()
    return {
        "metrics_text": get(f"{metrics}/metrics/prometheus"),
        "flight": json.loads(get(f"{metrics}/debug/flight-recorder")),
        "projection": json.loads(get(f"{metrics}/debug/projection")),
    }


def test_rpc_stage_histogram_has_stage_decomposition(scrape):
    stages = set(
        re.findall(r'keto_rpc_stage_seconds_count\{[^}]*stage="([^"]+)"',
                   scrape["metrics_text"])
    )
    # transport stages from REST + coalescer decomposition underneath
    assert {"parse", "compute", "encode"} <= stages
    assert len(stages) >= 4, stages
    ops = set(
        re.findall(r'keto_rpc_stage_seconds_count\{[^}]*op="([^"]+)"',
                   scrape["metrics_text"])
    )
    assert {"check", "expand"} <= ops


def test_engine_phase_histogram_present(scrape):
    phases = set(
        re.findall(r'keto_engine_phase_seconds_count\{phase="([^"]+)"\}',
                   scrape["metrics_text"])
    )
    assert any(p.startswith("check_") for p in phases), phases
    assert any(p.startswith("expand_") for p in phases), phases


def test_per_shard_gauges_present(scrape):
    text = scrape["metrics_text"]
    for g in (
        "keto_mesh_shard_batches",
        "keto_mesh_shard_fallbacks",
        "keto_mesh_shard_overlay_pairs",
        "keto_mesh_shard_nodes",
    ):
        assert f'{g}{{shard="0"}}' in text, g
    assert "keto_engine_dispatches" in text
    assert "keto_grpc_request_duration_seconds" in text


def test_flight_recorder_debug_endpoint(scrape):
    slowest = scrape["flight"]["slowest"]
    assert slowest, "flight recorder should have captured the smoke traffic"
    ops = {e["op"] for e in slowest}
    assert "check" in ops
    entry = max(slowest, key=lambda e: e["total_ms"])
    assert entry["stages_ms"]  # a stage vector rode along
    assert entry["total_ms"] >= max(entry["stages_ms"].values())


def test_batch_and_wire_metric_vocabulary(scrape):
    """ISSUE 7: the batch front door and the framed worker wire publish
    their metric vocabulary — batch RPC counts, items-per-batch, and
    socket bytes by direction on both wire endpoints."""
    text = scrape["metrics_text"]
    for op in ("check", "expand"):
        assert f'keto_batch_requests_total{{op="{op}"}}' in text, op
    assert "keto_batch_size" in text
    for d in ("tx", "rx"):
        assert f'keto_wire_bytes_total{{dir="{d}"}}' in text, d
    assert 'keto_wire_calls_total{op="check"}' in text


def test_columnar_metric_vocabulary(scrape):
    """ISSUE 9: the columnar batch path publishes its vocabulary — the
    columnar batch counter and the four stage timers on the check op
    (decode / encode_ids / wave_wait / respond)."""
    text = scrape["metrics_text"]
    assert "keto_columnar_batches_total" in text
    stages = set(
        re.findall(
            r'keto_rpc_stage_seconds_count\{[^}]*op="check"[^}]*'
            r'stage="([^"]+)"',
            text,
        )
        + re.findall(
            r'keto_rpc_stage_seconds_count\{[^}]*stage="([^"]+)"[^}]*'
            r'op="check"',
            text,
        )
    )
    assert {"decode", "encode_ids", "wave_wait", "respond"} <= stages, stages


def test_expand_rung_vocabulary(scrape):
    """What answered each Expand root: the smoke's tree fits the first
    rung of level capacities, so neither the full rung nor the oracle
    answered one."""
    text = scrape["metrics_text"]
    for rung in ("first", "full", "oracle"):
        assert f'keto_engine_expand_roots_total{{rung="{rung}"}}' in text
    roots = scrape["projection"]["expand_roots"]
    assert roots["first"] >= 1 and roots["full"] == roots["oracle"] == 0


def test_fast_rung_vocabulary(scrape):
    """Each rung a folded level of the fast BFS can run at is on the
    scrape (the smoke's depth-5 wave folds none)."""
    text = scrape["metrics_text"]
    for rung in ("quarter", "roots", "full"):
        assert f'keto_fused_fast_rung_levels_total{{rung="{rung}"}}' in text


def test_leopard_rows_vocabulary(scrape):
    """Every row the closure index is asked about lands in one outcome of
    ``keto_leopard_rows_total``; each outcome is on the scrape."""
    text = scrape["metrics_text"]
    for outcome in ("answered", "tainted", "ineligible", "beyond_depth",
                    "dirty"):
        assert f'keto_leopard_rows_total{{outcome="{outcome}"}}' in text


def test_projection_metric_vocabulary(scrape):
    """ISSUE 8: projection/compaction observability — generation and
    fold/rebuild/compaction counters as gauges, per-phase build seconds,
    overlay occupancy, and the /debug/projection state endpoint."""
    text = scrape["metrics_text"]
    for g in (
        "keto_projection_generation",
        "keto_projection_rebuilds_total",
        "keto_projection_folds_total",
        "keto_projection_compactions_total",
        "keto_projection_compaction_errors_total",
        "keto_projection_compaction_in_flight",
        "keto_projection_pending_changes",
        "keto_projection_overlay_pairs",
        "keto_projection_overlay_occupancy",
        "keto_projection_phase_seconds",
        # PR 34: the served hash tables and the tag invariant's counter
        'keto_projection_table_rounds{table="nt"}',
        'keto_projection_table_lookup_gathers{table="mt"}',
        'keto_projection_table_tag_salt{table="ovt"}',
        'keto_projection_tag_rejects_total{op="splice"}',
        # PR 36: what the build split to keep a lookup at four rounds
        'keto_projection_table_split_buckets{table="nt"}',
        'keto_projection_table_split_level_max{table="mt"}',
        'keto_projection_table_pad_slots{table="om"}',
        'keto_projection_tag_rejects_total{op="split"}',
        # PR 35: what the served projection takes on the device, the
        # gathers a lookup cost the fused waves, the host's lazy builds
        'keto_projection_device_bytes{group="node_table",kind="padded"}',
        'keto_projection_device_bytes{group="csr",kind="live"}',
        'keto_fused_probe_gathers_total{table="nt"}',
        'keto_fused_probe_gathers_total{table="om"}',
        'keto_host_lazy_build_seconds_total{what="vocab_index"}',
        'keto_host_lazy_build_seconds_total{what="store_fwd"}',
        # PR 37: every tick of the scheduling probe, however short
        "keto_host_sched_lag_seconds_total ",
        "keto_host_sched_ticks_total ",
    ):
        assert g in text, g
    proj = scrape["projection"]
    assert {"csr", "node_table", "membership_table", "overlay", "leopard",
            "membership", "expand_only", "mesh_only"} == set(
                proj["device_bytes"])
    assert all(0 <= g["live"] <= g["padded"]
               for g in proj["device_bytes"].values())
    assert set(proj["tables"]) == {"nt", "mt", "ovt", "om"}
    assert all(
        t["lookup_gathers"] <= t["rounds"] + 3 and t["tag_salt"] == 0
        and t["rounds"] == 4
        for t in proj["tables"].values()
    )
    assert all(
        {"split_buckets", "split_level_max", "pad_slots"} <= set(t)
        and t["split_level_max"] <= 7 and (t["split_buckets"] > 0) == (
            t["split_level_max"] > 0)
        for t in proj["tables"].values()
    )
    assert all(proj["tables"][p]["split_buckets"] == 0 for p in ("ovt", "om"))
    assert set(proj["tag_rejects"]) == {"build", "splice", "overlay", "split"}
    assert proj["generation"] >= 1
    assert proj["rebuilds"] >= 1  # the boot projection
    assert proj["served_cursor"] == proj["log_cursor"]
    assert "build_phases" in proj and proj["build_phases"]


def test_wave_ring_gauges_gone_and_window_series_present(scrape):
    """ISSUE 27: the four wave-ledger ring quantiles are off the scrape
    (a caller's window reads the same from counters and stages that are);
    the waits nobody timed are on it: the pool-wait stage of both front
    doors and the wave threads' states."""
    text = scrape["metrics_text"]
    for gone in ("keto_wave_size_mean", "keto_wave_size_p95",
                 "keto_wave_window_wait_ms_p50", "keto_wave_device_ms_p50"):
        assert gone not in text, gone
    assert "keto_engine_coalesced_checks" in text
    assert "keto_engine_coalesced_waves" in text
    stages = set(re.findall(
        r'keto_rpc_stage_seconds_count\{op="check",stage="([^"]+)"', text))
    assert {"pool_wait", "coalesce_wait", "device_compute"} <= stages, stages
    # PR 37: the gRPC check's receive, wake and send
    assert {"receive", "wake", "send"} <= stages, stages
    states = set(re.findall(
        r'keto_coalescer_thread_seconds\{state="([^"]+)",thread="([^"]+)"',
        text))
    assert {("idle", "collector"), ("window", "collector"),
            ("stage_empty", "dispatcher"), ("serve", "dispatcher"),
            ("file", "dispatcher")} <= states, states


def test_metric_vocabulary_documented_in_readme(scrape):
    """Vocabulary drift gate: every ``keto_*`` metric name a live daemon
    exposes must appear in README.md's metric table (wildcard rows like
    ``keto_engine_*`` cover their whole prefix).  A new metric that ships
    without documentation fails here, listing the missing names."""
    import os

    names = set()
    for line in scrape["metrics_text"].splitlines():
        if not line.startswith("keto_"):
            continue
        name = re.match(r"keto_[a-z0-9_]+", line).group(0)
        for suffix in ("_bucket", "_count", "_sum"):
            if name.endswith(suffix):
                name = name[: -len(suffix)]
                break
        names.add(name)
    assert names, "scrape produced no keto_* series"
    readme_path = os.path.join(
        os.path.dirname(__file__), "..", "README.md"
    )
    with open(readme_path, encoding="utf-8") as f:
        readme = f.read()
    wildcards = [
        w[:-1] for w in re.findall(r"keto_[a-z0-9_]+_\*", readme)
    ]
    missing = sorted(
        n for n in names
        if n not in readme and not any(n.startswith(p) for p in wildcards)
    )
    assert not missing, (
        f"metrics exposed by a live daemon but absent from README.md's "
        f"vocabulary table: {missing}"
    )


def test_trace_and_shadow_metric_vocabulary(scrape):
    """The request-anatomy observatory's vocabulary is live on a fresh
    daemon: trace-store counters (pre-registered at 0) and the shadow
    plane's checks/divergence/skip counters + sampled gauges."""
    text = scrape["metrics_text"]
    for m in (
        "keto_trace_completed_total",
        "keto_trace_promoted_total",
        "keto_trace_store_promoted",
        "keto_trace_store_recent",
        "keto_shadow_checks_total",
        "keto_shadow_divergence_total",
        "keto_shadow_skipped_total",
        "keto_shadow_queue_depth",
        "keto_shadow_divergence_ledger_size",
    ):
        assert m in text, m


def test_mesh_serving_metric_vocabulary(scrape):
    # ISSUE 10: replication / rebalance / failover gauges are part of the
    # stable scrape vocabulary even on a single-device engine (zeros), so
    # dashboards need one query either way
    text = scrape["metrics_text"]
    for g in ("keto_mesh_replica_keys", "keto_mesh_shard_down"):
        assert f'{g}{{shard="0"}}' in text, g
    for g in (
        "keto_mesh_replica_routed",
        "keto_mesh_replications",
        "keto_mesh_rebalances",
        "keto_mesh_shard_recoveries",
        "keto_mesh_load_skew",
    ):
        assert g in text, g
