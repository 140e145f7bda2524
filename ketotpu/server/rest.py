"""REST routes over the handler cores (httprouter/negroni analog).

Implements the reference's HTTP surface with its status-code quirks:

read port (`daemon.go:329-366`):
  GET/POST /relation-tuples/check            403-mirror (handler.go:121-154)
  GET/POST /relation-tuples/check/openapi    always 200 (handler.go:99-110)
  GET      /relation-tuples/expand           (expand/handler.go:62-111)
  GET      /relation-tuples                  (read_server.go:110-199)
  GET      /namespaces                       (namespacehandler/handler.go:39)
write port (`daemon.go:367-403`):
  PUT      /admin/relation-tuples            201 + Location (transact_server.go:134-176)
  DELETE   /admin/relation-tuples            204, query-validated (:188-243)
  PATCH    /admin/relation-tuples            204 (:245-309)
opl port (`daemon.go:405-440`):
  POST     /opl/syntax/check                 (schema/handler.go:38-45)
every port (healthx + metrics, `registry_default.go:128-182`):
  GET /health/alive, /health/ready, /version, /metrics/prometheus

Errors are herodot-shaped JSON: ``{"error": {"code", "status", "message"}}``.
"""

from __future__ import annotations

import json
import time
from typing import Callable, Dict, Optional, Tuple
from urllib.parse import urlencode

from ketotpu import consistency, flightrec
from ketotpu.cache import context as cache_context
from ketotpu.engine import columns
from ketotpu.api.types import (
    BadRequestError,
    KetoAPIError,
    NotFoundError,
    RelationQuery,
    RelationTuple,
    SubjectSet,
)
from ketotpu.observability import RELATIONTUPLES_CREATED

_STATUS_TEXT = {
    200: "OK",
    201: "Created",
    204: "No Content",
    400: "Bad Request",
    403: "Forbidden",
    404: "Not Found",
    405: "Method Not Allowed",
    412: "Precondition Failed",
    429: "Too Many Requests",
    500: "Internal Server Error",
    503: "Service Unavailable",
    504: "Gateway Timeout",
}

# requests that must work even when admission control is shedding: probes
# and scrapes are how operators see the overload.  The watch stream is
# exempt BY DESIGN, not oversight: a long-lived SSE stream parked on a
# heartbeat would pin an admission slot forever and starve point reads;
# the watch hub's own watch.max_subscribers cap bounds subscribers
# instead (excess subscribes get 429 from the hub).
_ADMISSION_EXEMPT = {
    "/health/alive", "/health/ready", "/version", "/metrics/prometheus",
    "/relation-tuples/watch",
    # the introspection probes exist to diagnose overload — shedding them
    # while shedding traffic would blind the operator exactly when the
    # surfaces matter most
    "/debug/flight-recorder", "/debug/waves", "/debug/compiles",
    "/debug/profile", "/debug/projection", "/debug/mesh",
    "/debug", "/debug/trace", "/debug/divergence", "/debug/handoff",
    "/debug/slo", "/debug/fleet", "/debug/incidents", "/debug/overload",
    "/debug/tenants",
}

# REST paths that get the full stage decomposition (flightrec context);
# everything else still gets the http duration histogram + access log
_RPC_OPS = {
    "/relation-tuples/check": "check",
    "/relation-tuples/check/openapi": "check",
    "/relation-tuples/check/batch": "check",
    "/relation-tuples/batch/check": "check",
    "/relation-tuples/batch/expand": "expand",
    "/relation-tuples/expand": "expand",
    "/relation-tuples/list-objects": "list_objects",
    "/relation-tuples/list-subjects": "list_subjects",
    "/relation-tuples/watch": "watch",
}

# admin DELETE rejects unknown query params (internal/x/validate, used at
# transact_server.go:193-199); these are ketoapi.RelationQueryKeys
_QUERY_KEYS = {
    "namespace", "object", "relation",
    "subject_id", "subject_set.namespace", "subject_set.object",
    "subject_set.relation",
}


def _flatten_query(qs: Dict[str, list]) -> Dict[str, str]:
    return {k: v[0] for k, v in qs.items() if v}


def _consistency_params(q: Dict[str, str]):
    """(snaptoken, latest) read-consistency query params.  `latest` takes
    the usual REST boolean spellings; anything else is a client bug."""
    token = q.get("snaptoken") or None
    raw = q.get("latest")
    if raw is None:
        return token, False
    if raw.lower() in ("true", "1", "yes", ""):
        return token, True
    if raw.lower() in ("false", "0", "no"):
        return token, False
    raise BadRequestError(
        f"unable to parse 'latest' query parameter as bool: {raw!r}"
    )


def _batch_consistency(body: dict, q: Dict[str, str]):
    """(snaptoken, latest) for a batch request: ONE consistency mode for
    the whole batch, from the JSON body (preferred) or query params."""
    token, latest = _consistency_params(q)
    if body.get("snaptoken"):
        token = str(body["snaptoken"])
    if body.get("latest") is not None:
        latest = bool(body["latest"])
    return token, latest


class StreamingResponse:
    """Route payload for long-lived streaming responses (the SSE watch
    stream): instead of buffering a body, the HTTP handler writes chunks
    as ``iterator`` yields them and closes the connection afterwards."""

    def __init__(self, iterator, content_type: str = "text/event-stream"):
        self.iterator = iterator
        self.content_type = content_type


def _max_depth(q: Dict[str, str]) -> int:
    """x/max_depth.go:13-24 parity incl. the bad-request error text.

    The reference parses with Go's base-0 syntax (strconv.ParseInt(s, 0, 0)):
    hex "0x10" is 16 and bare leading-zero "010" is octal 8.  Python's
    int(s, 0) matches except that it rejects the bare-leading-zero octal
    form as ambiguous, so that case is handled explicitly."""
    if "max-depth" not in q:
        return 0
    s = q["max-depth"]
    try:
        return int(s, 0)
    except ValueError:
        core = s.lstrip("+-")
        if core.startswith("0") and core.isdigit():
            try:
                v = int(core, 8)
            except ValueError:  # "089": invalid octal in Go base-0 too
                pass
            else:
                return -v if s.startswith("-") else v
        raise BadRequestError(
            f"unable to parse 'max-depth' query parameter to int: "
            f"invalid syntax {s!r}"
        ) from None


def cors_headers(
    cors: Dict, origin: Optional[str], *,
    request_method: Optional[str] = None, preflight: bool = False,
) -> Optional[Dict[str, str]]:
    """rs/cors-shaped decision (the reference wires rs/cors per port,
    `internal/driver/daemon.go:230-265` + `embedx/config.schema.json:
    214-259`): response headers for an allowed origin, None otherwise."""
    import fnmatch

    if not cors or origin is None:
        return None
    allowed = any(
        o == "*" or fnmatch.fnmatch(origin, o)
        for o in cors["allowed_origins"]
    )
    if not allowed:
        return None
    h = {"Vary": "Origin"}
    wildcard = "*" in cors["allowed_origins"] and not cors["allow_credentials"]
    h["Access-Control-Allow-Origin"] = "*" if wildcard else origin
    if cors["allow_credentials"]:
        h["Access-Control-Allow-Credentials"] = "true"
    if preflight:
        methods = [m.upper() for m in cors["allowed_methods"]]
        if request_method and request_method.upper() not in methods:
            return None
        h["Access-Control-Allow-Methods"] = ", ".join(methods)
        h["Access-Control-Allow-Headers"] = ", ".join(cors["allowed_headers"])
        if cors.get("max_age"):
            h["Access-Control-Max-Age"] = str(cors["max_age"])
    elif cors.get("exposed_headers"):
        h["Access-Control-Expose-Headers"] = ", ".join(
            cors["exposed_headers"]
        )
    return h


class Router:
    """Method+path exact-match routing table shared by all ports."""

    def __init__(self, registry, endpoint: str):
        self.r = registry
        self.endpoint = endpoint
        cors_for = getattr(registry.config, "cors_config", None)
        self.cors = cors_for(endpoint) if cors_for else None
        self.routes: Dict[Tuple[str, str], Callable] = {}
        # one-line operator docs per route; /debug derives its index from
        # these so a new surface can never be forgotten from the listing
        self.route_docs: Dict[Tuple[str, str], str] = {}
        self._register_common()

    def add(self, method: str, path: str, fn: Callable,
            describe: Optional[str] = None) -> None:
        self.routes[(method, path)] = fn
        if describe:
            self.route_docs[(method, path)] = describe

    def debug_surfaces(self) -> Dict[str, str]:
        """{path: one-liner} for every routed /debug/* surface (the
        /debug index body) — generated from the routing table, so the
        index and the routes cannot drift apart."""
        surfaces: Dict[str, str] = {}
        for (method, path) in sorted(self.routes):
            if path == "/debug" or not path.startswith("/debug/"):
                continue
            doc = self.route_docs.get((method, path), "")
            if method != "GET" and not doc.startswith(method):
                doc = f"{method}: {doc}" if doc else method
            surfaces[path] = doc
        return surfaces

    # -- common routes (healthx + metrics on every router) -------------------

    def _register_common(self) -> None:
        self.add("GET", "/health/alive", self._alive)
        self.add("GET", "/health/ready", self._ready)
        self.add("GET", "/version", self._version)
        self.add("GET", "/metrics/prometheus", self._metrics)

    def _alive(self, req) -> Tuple[int, object]:
        return 200, {"status": "ok"}

    def _ready(self, req) -> Tuple[int, object]:
        health = self.r.health()
        errors = {k: v for k, v in health.items() if v != "ok"}
        if not errors:
            return 200, {"status": "ok"}
        # degraded-only (device engine on CPU fallback, worker respawning):
        # still ready — answering traffic is the point of degrading — but
        # surfaced so `status --block` can tell degraded from down
        if all(str(v).startswith("degraded") for v in errors.values()):
            return 200, {"status": "degraded", "degraded": errors}
        return 503, {"errors": errors}

    def _version(self, req) -> Tuple[int, object]:
        return 200, {"version": self.r.version}

    def _metrics(self, req) -> Tuple[int, object]:
        sample = getattr(self.r, "sample_engine_metrics", None)
        if sample is not None:
            sample()  # refresh device-engine gauges at scrape time
        return 200, ("text/plain; version=0.0.4", self.r.metrics().exposition())

    # -- dispatch ------------------------------------------------------------

    def dispatch(self, method: str, path: str, req) -> Tuple[int, object, Dict]:
        try:
            # embedder middlewares run outermost (negroni-style chain,
            # ketoctx WithHTTPMiddlewares); each gets a zero-arg `next`
            chain = lambda: self._route(method, path, req)  # noqa: E731
            for mw in reversed(self.r.options.rest_middlewares):
                chain = (lambda m, nxt: lambda: m(method, path, req, nxt))(
                    mw, chain
                )
            return chain()
        except KetoAPIError as e:
            code = e.status_code or 500
            # shed responses carry the backoff hint the reference's
            # rate-limit middlewares send — load-derived + jittered so a
            # shed cohort does not stampede back in lockstep
            headers = (
                {"Retry-After": self.r.retry_after_hint()}
                if code in (429, 503) else {}
            )
            return code, _error_body(code, str(e)), headers
        except Exception as e:  # noqa: BLE001 - the panic-recovery interceptor
            self.r.logger().exception("handler panic: %s", e)
            return 500, _error_body(500, str(e)), {}

    def _route(self, method: str, path: str, req) -> Tuple[int, object, Dict]:
        fn = self.routes.get((method, path))
        if fn is None:
            known_methods = [m for (m, p) in self.routes if p == path]
            if known_methods:
                return 405, _error_body(405, "method not allowed"), {}
            return 404, _error_body(404, "route not found"), {}
        out = fn(req)
        if len(out) == 2:
            status, body = out
            headers: Dict[str, str] = {}
        else:
            status, body, headers = out
        return status, body, headers


def _error_body(code: int, message: str) -> dict:
    return {
        "error": {
            "code": code,
            "status": _STATUS_TEXT.get(code, "error"),
            "message": message,
        }
    }


class Request:
    """Parsed request handed to route functions."""

    def __init__(
        self,
        query: Dict[str, str],
        body: bytes,
        headers: Optional[Dict[str, str]] = None,
    ):
        self.query = query
        self.body = body
        self.headers = headers or {}  # lower-cased names

    def json(self):
        try:
            return json.loads(self.body.decode("utf-8") or "null")
        except (ValueError, UnicodeDecodeError) as e:
            raise BadRequestError(f"could not unmarshal json: {e}") from None


# -- route construction per port ---------------------------------------------


def read_router(registry) -> Router:
    from ketotpu.server.handlers import (
        CheckHandler,
        ExpandHandler,
        NamespaceHandler,
        RelationTupleHandler,
    )

    rt = Router(registry, "read")
    check = CheckHandler(registry)
    expand = ExpandHandler(registry)
    tuples = RelationTupleHandler(registry)
    namespaces = NamespaceHandler(registry)

    def get_check(mirror: bool):
        def handler(req):
            tuple_ = RelationTuple.from_url_query(req.query)
            token, latest = _consistency_params(req.query)
            allowed = check.check_rest(
                tuple_, _max_depth(req.query), req.headers,
                snaptoken=token, latest=latest,
            )
            status = 403 if (mirror and not allowed) else 200
            return status, {"allowed": allowed}

        return handler

    def post_check(mirror: bool):
        def handler(req):
            tuple_ = RelationTuple.from_json(req.json() or {})
            token, latest = _consistency_params(req.query)
            allowed = check.check_rest(
                tuple_, _max_depth(req.query), req.headers,
                snaptoken=token, latest=latest,
            )
            status = 403 if (mirror and not allowed) else 200
            return status, {"allowed": allowed}

        return handler

    rt.add("GET", "/relation-tuples/check", get_check(mirror=True))
    rt.add("POST", "/relation-tuples/check", post_check(mirror=True))
    rt.add("GET", "/relation-tuples/check/openapi", get_check(mirror=False))
    rt.add("POST", "/relation-tuples/check/openapi", post_check(mirror=False))

    def post_check_batch(req):
        # EXTENSION endpoint (no reference counterpart): one request, many
        # verdicts, answered by the engine's batched device dispatch
        body = req.json()
        if not isinstance(body, dict) or not isinstance(
            body.get("tuples"), list
        ):
            raise BadRequestError('expected {"tuples": [...]}')
        tuples_in = [RelationTuple.from_json(d or {}) for d in body["tuples"]]
        r = registry.resolve(req.headers)
        token, latest = _consistency_params(req.query)
        decoded = None
        if token or latest:
            decoded = consistency.ensure_fresh(r, token, latest, op="check")
        with cache_context.request_scope(r, req.headers, token=decoded,
                                         latest=latest):
            results = check.batch_check_core(
                tuples_in, _max_depth(req.query), r
            )
        return 200, {
            "results": [{"allowed": a} for a in results],
            "snaptoken": check.snaptoken(r),
        }

    rt.add("POST", "/relation-tuples/check/batch", post_check_batch)

    def post_batch_check(req):
        # batch front door (ISSUE 7): per-item verdicts/errors, one shared
        # consistency mode + snaptoken, per-item admission accounting.
        # Supersedes /relation-tuples/check/batch (kept for compat).
        from ketotpu.server.handlers import batch_admission, record_batch

        body = req.json()
        if not isinstance(body, dict) or not isinstance(
            body.get("tuples"), list
        ):
            raise BadRequestError('expected {"tuples": [...]}')
        raw = body["tuples"]
        r = registry.resolve(req.headers)
        # COLUMNAR by default (ISSUE 9): the raw tuples list is decoded
        # once into string columns, answered as one block through the
        # engine, and the response frame is scattered from the verdict
        # array in two bytes.join passes — engine.columnar_batch=false
        # restores the per-item scalar path.
        columnar = bool(r.config.get("engine.columnar_batch", True))
        token, latest = _batch_consistency(body, req.query)
        depth = body.get("max_depth")
        depth = int(depth) if depth is not None else _max_depth(req.query)
        flightrec.note(batch=len(raw))
        record_batch(r, "check", len(raw))
        with batch_admission(r, len(raw)):
            decoded = None
            if token or latest:
                decoded = consistency.ensure_fresh(
                    r, token, latest, op="check"
                )
            with cache_context.request_scope(r, req.headers, token=decoded,
                                             latest=latest):
                if columnar:
                    allowed, errors = check.batch_check_columnar(
                        raw, depth, r
                    )
                else:
                    items = []
                    for d in raw:
                        try:
                            # a bad tuple becomes ITS item's error, not
                            # the batch's
                            items.append(RelationTuple.from_json(d or {}))
                        except KetoAPIError as e:
                            items.append(e)
                    results = check.batch_check_items(items, depth, r)
        if not columnar:
            return 200, {
                "results": results,
                "snaptoken": check.snaptoken(r),
            }
        t0 = time.perf_counter()
        frags = columns.verdict_fragments(allowed)
        for i, err in errors.items():
            frags[i] = columns.error_fragment(err[0], err[1])
        data = columns.render_batch_body(frags, check.snaptoken(r))
        flightrec.note_stage("respond", time.perf_counter() - t0)
        return 200, ("application/json", data)

    rt.add("POST", "/relation-tuples/batch/check", post_batch_check)

    def post_batch_expand(req):
        from ketotpu.server.handlers import batch_admission, record_batch

        body = req.json()
        if not isinstance(body, dict) or not isinstance(
            body.get("subjects"), list
        ):
            raise BadRequestError('expected {"subjects": [...]}')
        items = []
        for d in body["subjects"]:
            if not isinstance(d, dict):
                items.append(BadRequestError("subject must be an object"))
                continue
            items.append(SubjectSet(
                namespace=str(d.get("namespace", "")),
                object=str(d.get("object", "")),
                relation=str(d.get("relation", "")),
            ))
        r = registry.resolve(req.headers)
        token, latest = _batch_consistency(body, req.query)
        depth = body.get("max_depth")
        depth = int(depth) if depth is not None else _max_depth(req.query)
        flightrec.note(batch=len(items))
        record_batch(r, "expand", len(items))
        with batch_admission(r, len(items)):
            decoded = None
            if token or latest:
                decoded = consistency.ensure_fresh(
                    r, token, latest, op="expand"
                )
            with cache_context.request_scope(r, req.headers, token=decoded,
                                             latest=latest):
                results = expand.batch_expand_items(items, depth, r)
        enc = []
        for res in results:
            if "tree" in res:
                if res["tree"] is None:
                    enc.append({
                        "error": "no relation tuple found", "status": 404,
                    })
                else:
                    enc.append({"tree": res["tree"].to_json()})
            else:
                enc.append(res)
        return 200, {
            "results": enc,
            "snaptoken": consistency.mint(
                r.store(), r._device_engine()
            ).encode(),
        }

    rt.add("POST", "/relation-tuples/batch/expand", post_batch_expand)

    def get_expand(req):
        subject = SubjectSet(
            namespace=req.query.get("namespace", ""),
            object=req.query.get("object", ""),
            relation=req.query.get("relation", ""),
        )
        r = registry.resolve(req.headers)
        token, latest = _consistency_params(req.query)
        decoded = None
        if token or latest:
            decoded = consistency.ensure_fresh(r, token, latest, op="expand")
        with cache_context.request_scope(r, req.headers, token=decoded,
                                         latest=latest):
            tree = expand.expand_core(subject, _max_depth(req.query), r)
        if tree is None:
            return 404, _error_body(404, "no relation tuple found")
        return 200, tree.to_json()

    rt.add("GET", "/relation-tuples/expand", get_expand)

    def get_relations(req):
        query = RelationQuery.from_url_query(req.query)
        page_size = 0
        if "page_size" in req.query:
            try:
                page_size = int(req.query["page_size"])
            except ValueError as e:
                raise BadRequestError(str(e)) from None
        r = registry.resolve(req.headers)
        token, latest = _consistency_params(req.query)
        if token or latest:
            # list reads the store directly, so the barrier only needs
            # the store to have reached the token — not the device view
            consistency.ensure_fresh(
                r, token, latest, op="list", use_engine=False
            )
        out, next_token = tuples.list_core(
            query, page_size, req.query.get("page_token", ""), r,
        )
        return 200, {
            "relation_tuples": [t.to_json() for t in out],
            "next_page_token": next_token,
        }

    rt.add("GET", "/relation-tuples", get_relations)

    def _page_args(req):
        page_size = 0
        if "page_size" in req.query:
            try:
                page_size = int(req.query["page_size"])
            except ValueError as e:
                raise BadRequestError(str(e)) from None
        return page_size, req.query.get("page_token", "")

    def get_list_objects(req):
        # Leopard reverse query: objects the subject reaches in
        # namespace#relation through the closure index (host-oracle
        # fallback on dirty sets).  Rows come back as full relation
        # tuples so clients reuse the ListRelationTuples decoding.
        query = RelationQuery.from_url_query(req.query)
        page_size, page_token = _page_args(req)
        objs, next_token = tuples.list_objects_core(
            query.namespace, query.relation, query.subject(),
            page_size, page_token, registry.resolve(req.headers),
        )
        subject = query.subject()
        return 200, {
            "relation_tuples": [
                RelationTuple(
                    query.namespace, o, query.relation, subject
                ).to_json()
                for o in objs
            ],
            "objects": objs,
            "next_page_token": next_token,
        }

    def get_list_subjects(req):
        query = RelationQuery.from_url_query(req.query)
        page_size, page_token = _page_args(req)
        subs, next_token = tuples.list_subjects_core(
            query.namespace, query.object, query.relation,
            page_size, page_token, registry.resolve(req.headers),
        )
        return 200, {
            "relation_tuples": [
                RelationTuple(
                    query.namespace, query.object, query.relation, s
                ).to_json()
                for s in subs
            ],
            "next_page_token": next_token,
        }

    rt.add("GET", "/relation-tuples/list-objects", get_list_objects)
    rt.add("GET", "/relation-tuples/list-subjects", get_list_subjects)

    def get_namespaces(req):
        return 200, {
            "namespaces": [{"name": ns.name} for ns in namespaces.list_core()]
        }

    rt.add("GET", "/namespaces", get_namespaces)

    def get_watch(req):
        # EXTENSION endpoint: Zanzibar Watch over SSE.  Subscribe before
        # returning so subscribe-time errors (bad token, subscriber cap)
        # still come back as ordinary JSON error bodies; only once the
        # stream is live do errors degrade to a dropped connection.
        r = registry.resolve(req.headers)
        hub = r.watch_hub()
        sub = hub.subscribe(
            snaptoken=req.query.get("snaptoken") or None,
            namespace=req.query.get("namespace") or None,
        )
        flightrec.note(resume=bool(req.query.get("snaptoken")))
        heartbeat_s = (
            float(r.config.get("watch.heartbeat_ms", 15000) or 15000)
            / 1000.0
        )

        def gen():
            try:
                # SSE comment line: flushes proxy buffers and lets the
                # client see the stream is open before the first event
                yield b": watch stream open\n\n"
                for ev in sub.events(heartbeat_s):
                    data = {"snaptoken": ev.snaptoken or ""}
                    if ev.kind == consistency.DELTA:
                        data["action"] = ev.action
                        data["relation_tuple"] = ev.tuple.to_json()
                    yield (
                        f"event: {ev.kind}\n"
                        f"data: {json.dumps(data)}\n\n"
                    ).encode("utf-8")
            finally:
                hub.unsubscribe(sub)

        return 200, StreamingResponse(gen())

    rt.add("GET", "/relation-tuples/watch", get_watch)
    return rt


def write_router(registry) -> Router:
    from ketotpu.server.handlers import RelationTupleHandler

    rt = Router(registry, "write")
    tuples = RelationTupleHandler(registry)

    def _post_write_token(r) -> str:
        # post-commit snaptoken, echoed in a response header so REST
        # writers can do read-your-writes without a second round trip
        return consistency.mint(r.store(), r._device_engine()).encode()

    def put_tuple(req):
        tuple_ = RelationTuple.from_json(req.json() or {})
        r = registry.resolve(req.headers)
        tuples.transact_core([tuple_], [], r)
        registry.tracer().event(RELATIONTUPLES_CREATED)
        # urlencode: raw values in a header invite response splitting
        location = "/relation-tuples?" + urlencode(tuple_.to_url_query())
        return 201, tuple_.to_json(), {
            "Location": location,
            "X-Keto-Snaptoken": _post_write_token(r),
        }

    def delete_tuples(req):
        # validate.All parity (transact_server.go:193-199)
        extra = set(req.query) - _QUERY_KEYS
        if extra:
            raise BadRequestError(
                f"unexpected query parameters: {sorted(extra)}"
            )
        if "namespace" not in req.query:
            raise BadRequestError("required query parameter 'namespace' is missing")
        if req.body:
            raise BadRequestError("the request body must be empty")
        query = RelationQuery.from_url_query(req.query)
        r = registry.resolve(req.headers)
        tuples.delete_all_core(query, r)
        return 204, None, {"X-Keto-Snaptoken": _post_write_token(r)}

    def patch_tuples(req):
        deltas = req.json()
        if not isinstance(deltas, list):
            raise BadRequestError("expected a JSON list of patch deltas")
        inserts, deletes = [], []
        for d in deltas:
            if not isinstance(d, dict) or d.get("relation_tuple") is None:
                raise BadRequestError("relation_tuple is missing")
            t = RelationTuple.from_json(d["relation_tuple"])
            action = d.get("action")
            if action == "insert":
                inserts.append(t)
            elif action == "delete":
                deletes.append(t)
            else:
                raise BadRequestError(f"unknown action {action}")
        r = registry.resolve(req.headers)
        tuples.transact_core(inserts, deletes, r)
        return 204, None, {"X-Keto-Snaptoken": _post_write_token(r)}

    rt.add("PUT", "/admin/relation-tuples", put_tuple)
    rt.add("DELETE", "/admin/relation-tuples", delete_tuples)
    rt.add("PATCH", "/admin/relation-tuples", patch_tuples)

    # -- tenant lifecycle (ketotpu/tenancy/): admin-port surface ----------

    def _plane():
        plane = registry.tenant_plane()
        if plane is None:
            raise NotFoundError(
                "tenancy is not enabled (set tenancy.enabled with the "
                "in-memory dsn)"
            )
        return plane

    def post_tenant(req):
        body = req.json() or {}
        nid = body.get("id")
        if not isinstance(nid, str) or not nid:
            raise BadRequestError("'id' is required")
        plane = _plane()
        out = plane.create(nid)
        opl = body.get("opl")
        if isinstance(opl, str) and opl.strip():
            out["opl"] = plane.set_opl(nid, opl)
        return (201 if out.get("created") else 200), out

    def get_tenants(req):
        return 200, {"tenants": _plane().catalog()}

    def delete_tenant(req):
        nid = req.query.get("id", "")
        if not nid:
            raise BadRequestError("required query parameter 'id' is missing")
        return 200, _plane().delete(nid)

    def post_tenant_opl(req):
        body = req.json() or {}
        nid = body.get("id")
        if not isinstance(nid, str) or not nid:
            raise BadRequestError("'id' is required")
        source = body.get("opl", "")
        if not isinstance(source, str):
            raise BadRequestError("'opl' must be a string (empty clears)")
        return 200, _plane().set_opl(nid, source)

    rt.add("POST", "/admin/tenants", post_tenant)
    rt.add("GET", "/admin/tenants", get_tenants)
    rt.add("DELETE", "/admin/tenants", delete_tenant)
    rt.add("POST", "/admin/tenants/opl", post_tenant_opl)
    return rt


def opl_router(registry) -> Router:
    from ketotpu.server.handlers import SyntaxHandler

    rt = Router(registry, "opl")
    syntax = SyntaxHandler(registry)

    def post_syntax(req):
        errors = syntax.check_core(req.body)
        return 200, {"errors": [e.to_json() for e in errors]}

    rt.add("POST", "/opl/syntax/check", post_syntax)
    return rt


def metrics_router(registry) -> Router:
    rt = Router(registry, "metrics")

    def get_flight_recorder(req):
        # debug surface on the metrics port only (admin-port hygiene):
        # the N slowest recent requests with their stage vectors, plus
        # the hot-spot shield's top-K hottest keys (count-min estimates)
        rec = registry.flight_recorder()
        rc = registry.result_cache()
        return 200, {
            "slowest": rec.snapshot(),
            "hot_keys": rc.hot_keys() if rc is not None else [],
            # a slow-check investigation usually starts with "was a
            # compaction in flight?" — ride the projection state along
            "projection": registry.projection_stats(),
        }

    rt.add("GET", "/debug/flight-recorder", get_flight_recorder,
           describe="N slowest recent requests with stage vectors + "
                    "hot keys")

    def get_waves(req):
        # wave ledger (ketotpu/waveledger.py): the last N dispatched
        # waves.  ?wave=<id> joins from a flight-recorder entry's wave=
        # field back to its wave; ?n= bounds the listing.  Each entry's
        # slowest[] traceparents join the other direction.
        ledger = registry.wave_ledger()
        wave = req.query.get("wave")
        n = req.query.get("n")
        try:
            wave = int(wave) if wave is not None else None
            n = int(n) if n is not None else None
        except ValueError:
            raise BadRequestError("wave and n must be integers")
        return 200, {
            "stats": ledger.stats(),
            "waves": ledger.snapshot(n=n, wave=wave),
        }

    rt.add("GET", "/debug/waves", get_waves,
           describe="wave ledger: recent device dispatch windows "
                    "(?wave=<id>)")

    def get_compiles(req):
        # XLA compile observatory (ketotpu/compilewatch.py): totals per
        # entry point + the bounded compile event log; `warm` tells
        # whether the next compile would fire the after-warm alarm
        return 200, registry.compile_watch().snapshot()

    rt.add("GET", "/debug/compiles", get_compiles,
           describe="XLA compile observatory: totals + bounded event log")

    def get_projection(req):
        # projection/compaction observability (engine/tpu.py): snapshot
        # generation, fold/rebuild/compaction counters, overlay occupancy
        # and the cursor triple (snap <= served <= log); {} when the
        # engine kind has no device projection
        return 200, registry.projection_stats()

    rt.add("GET", "/debug/projection", get_projection,
           describe="device projection: generation, folds, overlay, "
                    "cursors")

    def get_mesh(req):
        # sharded-serving state (parallel/meshengine.py): per-shard
        # batches/fallbacks/replica keys/down flags, the published
        # replica map, the replication/rebalance/failover counters, and
        # — on a multi-host topology — per-peer rows (id, liveness,
        # heartbeat age, shards owned, replica keys, frontier round
        # trips) so `status --debug` explains a degraded topology;
        # {} when the engine is not sharded
        eng = registry.check_engine()
        eng = getattr(eng, "inner", eng)
        stats_fn = getattr(eng, "mesh_stats", None)
        if stats_fn is None:
            return 200, {}
        peers_fn = getattr(eng, "peer_stats", None)
        return 200, {
            **stats_fn(),
            "shards": eng.shard_stats(),
            "replica_map": [
                {"ns": k[0], "obj": k[1], "replicas": list(v)}
                for k, v in sorted(eng._replica_map.items())
            ],
            "hosts": peers_fn() if peers_fn is not None else [],
        }

    rt.add("GET", "/debug/mesh", get_mesh,
           describe="sharded serving: per-shard state + replica map")

    def post_profile(req):
        # on-demand jax.profiler capture: config-gated (403 unarmed),
        # single-flight (409 while a capture runs), seconds clamped;
        # ?python=1 adds the Python tracer's stacks to the host spans
        from ketotpu.profiler import ProfilerBusy, ProfilerDisabled

        try:
            seconds = float(req.query.get("seconds", "5"))
        except ValueError:
            raise BadRequestError("seconds must be a number")
        try:
            artifact = registry.profiler().capture(
                seconds, python=req.query.get("python", "") in ("1", "true")
            )
        except ProfilerDisabled as e:
            return 403, {"error": {"code": 403, "message": str(e)}}
        except ProfilerBusy as e:
            return 409, {"error": {"code": 409, "message": str(e)}}
        return 200, artifact

    rt.add("POST", "/debug/profile", post_profile,
           describe="POST: on-demand jax.profiler capture (config-gated)")

    def post_handoff(req):
        # deliberate takeover (rolling restart): tells the warm-standby
        # follower attached to this registry to promote itself NOW instead
        # of waiting out the heartbeat-miss budget.  409 when no standby
        # machinery is wired (a plain owner/daemon process).
        fn = getattr(registry, "handoff_fn", None)
        if fn is None:
            return 409, {"error": {
                "code": 409,
                "message": "no standby attached to this process; handoff"
                           " is served by the follower's metrics port",
            }}
        reason = str(req.query.get("reason", "handoff") or "handoff")
        return 200, dict(fn(reason) or {}, reason=reason)

    rt.add("POST", "/debug/handoff", post_handoff,
           describe="POST: promote the attached warm standby now "
                    "(rolling restart; 409 when none)")

    def get_debug_index(req):
        # one stop for "what can I look at?": every debug surface on this
        # port with a one-liner, so an operator paging through an incident
        # doesn't need the README open to find the next probe.  Generated
        # from the routing table (Router.debug_surfaces) so adding a
        # surface automatically lists it here.
        return 200, {"surfaces": rt.debug_surfaces()}

    rt.add("GET", "/debug", get_debug_index)

    def get_trace(req):
        # the request-anatomy observatory's read side: newest promoted
        # traces (tail-sampled: slow/shed/deadline/error/divergence), or
        # one stitched cross-process timeline via ?trace=<id>
        ts = registry.trace_store()
        if ts is None:
            return 200, {"enabled": False, "traces": []}
        tid = req.query.get("trace")
        if tid:
            ent = ts.get(tid)
            if ent is None:
                raise NotFoundError(f"trace {tid!r} not held")
            return 200, ent
        n = req.query.get("n")
        try:
            n = int(n) if n is not None else 0
        except ValueError:
            raise BadRequestError("n must be an integer")
        return 200, {
            "enabled": True,
            "stats": ts.stats(),
            "traces": ts.promoted(n=n),
        }

    rt.add("GET", "/debug/trace", get_trace,
           describe="tail-sampled promoted traces (?trace=<id> for one "
                    "stitched timeline)")

    def get_divergence(req):
        # shadow-verification plane: the divergence ledger (each record
        # names the lying tier, wave, generation, and trace id) + sampler
        # stats; {} stats when the plane is off (workers, config)
        sh = registry.shadow()
        if sh is None:
            return 200, {"enabled": False, "divergences": [], "stats": {}}
        return 200, {
            "enabled": True,
            "stats": sh.stats(),
            "divergences": sh.ledger(),
        }

    rt.add("GET", "/debug/divergence", get_divergence,
           describe="shadow-verification divergence ledger + sampler "
                    "stats")

    def get_slo(req):
        # SLO burn-rate engine (ketotpu/slo.py): per-op availability and
        # latency-compliance SLIs over the fast (~5 min) and slow (~1 h)
        # windows, with the burn rate against the configured objectives
        slo = registry.slo()
        if slo is None:
            return 200, {"enabled": False}
        slo.sample()
        return 200, {"enabled": True, **slo.snapshot()}

    rt.add("GET", "/debug/slo", get_slo,
           describe="SLO burn rates: per-op availability/latency SLIs "
                    "over fast + slow windows")

    def get_fleet(req):
        # fleet health: this host's digest plus the last digest each DCN
        # peer shipped on its heartbeat.  A peer that has never sent one
        # (a pre-fleet-health binary) renders "unavailable" rather than
        # erroring — mixed-version meshes happen during rollouts.
        local = registry.health_digest()
        link = registry.hostlink()
        if link is None:
            return 200, {"multihost": False, "local": local, "peers": []}
        peers = []
        for row in link.peer_rows():
            digest = row.get("digest")
            peers.append({
                "peer": row.get("peer"),
                "addr": row.get("addr"),
                "down": row.get("down"),
                "heartbeat_age_s": row.get("heartbeat_age_s"),
                "digest": (
                    digest if isinstance(digest, dict) else "unavailable"
                ),
            })
        return 200, {"multihost": True, "local": local, "peers": peers}

    rt.add("GET", "/debug/fleet", get_fleet,
           describe="per-host health digests: local + last heartbeat "
                    "digest from every DCN peer")

    def get_incidents(req):
        # regression watchdog (ketotpu/watchdog.py): bounded incident
        # records, newest first; each names the firing rule, the detail
        # that tripped it, and the trace ids it force-promoted
        wd = registry.watchdog()
        if wd is None:
            return 200, {"enabled": False, "incidents": []}
        n = req.query.get("n")
        try:
            n = int(n) if n is not None else 0
        except ValueError:
            raise BadRequestError("n must be an integer")
        return 200, {
            "enabled": True,
            "stats": wd.stats(),
            "incidents": wd.incidents(n=n),
        }

    rt.add("GET", "/debug/incidents", get_incidents,
           describe="watchdog incidents: rule, detail, force-promoted "
                    "trace ids (newest first)")

    def get_overload(req):
        # overload-control plane (server/overload.py): ladder stage,
        # adaptive admission limit + per-class caps, AIMD signal sample,
        # breaker/retry-budget state and the recent transition log
        ov = registry.overload()
        if ov is None:
            ctl = registry.admission()
            return 200, {
                "enabled": False,
                "admission": ctl.snapshot() if ctl is not None else {},
            }
        return 200, {"enabled": True, **ov.snapshot()}

    rt.add("GET", "/debug/overload", get_overload,
           describe="overload plane: brownout stage, adaptive limit, "
                    "class caps, breakers, transitions")

    def get_tenants_debug(req):
        plane = registry.tenant_plane()
        if plane is None:
            return 200, {"enabled": False}
        return 200, {
            "enabled": True,
            **plane.stats(),
            "tenants": plane.catalog(),
        }

    rt.add("GET", "/debug/tenants", get_tenants_debug,
           describe="tenant plane: per-tenant tuples/traffic/quota "
                    "occupancy, OPL overrides, capacity")
    return rt


# -- HTTP server ------------------------------------------------------------


def make_http_server(router: Router, host: str, port: int,
                     reuse_port: bool = False, ssl_ctx=None):
    """Build the REST front end: an asyncio event-loop server (see
    server/aio.py) behind the lifecycle surface the daemon drives
    (``server_address`` / ``serve_forever`` / ``shutdown`` /
    ``server_close``).  ``reuse_port`` binds SO_REUSEPORT for the
    multi-process worker topology; ``ssl_ctx`` terminates TLS in the
    event loop (per-connection handshakes never block the accept loop).
    """
    from ketotpu.server.aio import AsyncHTTPServer

    return AsyncHTTPServer(
        router, host, port, reuse_port=reuse_port, ssl_ctx=ssl_ctx,
    )
