"""HTTP front door integration tests — a real localhost server, real
``http.client`` requests with NO repro imports on the client side of
the wire, driving the real coalescing/admission/observability stack.

The load-bearing assertions:

  * concurrent HTTP clients coalesce into SHARED flushes (the §3
    micro-batching win survives the network hop);
  * a 429 carries a parseable integer ``Retry-After`` and the taxonomy
    body (``code: overloaded``);
  * tenant-quota sheds CONSERVE: client-observed 429s == telemetry
    ``shed_requests`` == ``request.shed`` spans, and
    ``Tracer.conservation`` stays balanced;
  * alias hot-swap mid-traffic routes new requests to the new digest
    with zero failed requests;
  * ``/metrics`` parses as Prometheus text exposition.
"""

import base64
import http.client
import json
import re
import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import gamma_max
from repro.core.rbf import SVMModel
from repro.core.families import fourier, maclaurin
from repro.serve import PublishSpec, create_app
from repro.serve.runtime import FaultInjector, Runtime
from repro.serve.server import TenantConfig, serve

ENGINE_OPTS = dict(min_bucket=8, max_batch=64)


def _svm(seed=0, d=8, n_sv=40, bias=0.1):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n_sv, d)).astype(np.float32) * 0.6
    gamma = float(gamma_max(jnp.asarray(X))) * 0.8
    ay = rng.standard_normal(n_sv).astype(np.float32) * 0.5
    return SVMModel(X=jnp.asarray(X), alpha_y=jnp.asarray(ay),
                    b=jnp.float32(bias), gamma=jnp.float32(gamma))


def _rows(rng, n, d=8):
    return (rng.standard_normal((n, d)) * 0.3).tolist()


class _Client:
    """Tiny JSON-over-HTTP client: stdlib only, one connection, no
    repro imports — the acceptance criterion's 'external client'."""

    def __init__(self, host, port):
        self.conn = http.client.HTTPConnection(host, port, timeout=60)

    def request(self, method, path, body=None, headers=None):
        hdrs = dict(headers or {})
        data = None
        if body is not None:
            data = json.dumps(body).encode()
            hdrs["Content-Type"] = "application/json"
        self.conn.request(method, path, body=data, headers=hdrs)
        resp = self.conn.getresponse()
        raw = resp.read()
        parsed = json.loads(raw) if raw and resp.headers.get(
            "content-type", "").startswith("application/json") else raw
        return resp.status, parsed, {
            k.lower(): v for k, v in resp.headers.items()
        }

    def close(self):
        self.conn.close()


def _app_and_server(runtime=None, tenants=None, **runtime_kw):
    runtime_kw.setdefault("engine_opts", ENGINE_OPTS)
    runtime_kw.setdefault("warmup_on_load", False)
    app = create_app(runtime, tenants=tenants,
                     **(runtime_kw if runtime is None else {}))
    handle = serve(app)
    return app, handle


def _publish(app, model, alias, family=maclaurin, **spec_kw):
    art = family.compile(model)
    return app.runtime.publish(
        alias, art, PublishSpec(exact=model, **spec_kw)
    )


# ------------------------------------------------------------ basic contract


def test_predict_returns_scores_validity_and_digest():
    app, h = _app_and_server()
    with app, h:
        m = _svm(0)
        digest = _publish(app, m, "det")
        c = _Client(h.host, h.port)
        status, body, _ = c.request(
            "POST", "/v1/models/det:predict",
            {"rows": _rows(np.random.default_rng(0), 5)})
        assert status == 200
        assert body["digest"] == digest
        assert body["n"] == 5
        assert len(body["scores"]) == 5 and len(body["labels"]) == 5
        assert body["valid"] == [True] * 5          # in-envelope traffic
        assert body["family"] == "maclaurin"
        # digest-addressed and prefix-addressed refs serve identically
        status2, body2, _ = c.request(
            "POST", f"/v1/models/{digest[:12]}:predict",
            {"rows": _rows(np.random.default_rng(0), 5)})
        assert status2 == 200 and body2["scores"] == body["scores"]
        c.close()


def test_error_taxonomy_maps_onto_http():
    app, h = _app_and_server()
    with app, h:
        _publish(app, _svm(0), "det")
        c = _Client(h.host, h.port)
        cases = [
            ("POST", "/v1/models/nope:predict", {"rows": [[0.0] * 8]},
             404, "model_not_found"),
            ("POST", "/v1/models/det:predict", {"rowz": []},
             400, "invalid_request"),
            ("POST", "/v1/models/det:predict", None,
             400, "invalid_request"),          # empty body
            ("GET", "/v1/nowhere", None, 404, "not_found"),
            ("DELETE", "/v1/models", None, 405, "method_not_allowed"),
        ]
        for method, path, body, want_status, want_code in cases:
            status, parsed, _ = c.request(method, path, body)
            assert status == want_status, (path, status, parsed)
            assert parsed["error"]["code"] == want_code
            assert parsed["error"]["status"] == want_status
        c.close()


def test_http_publish_then_predict_no_repro_client_imports():
    """The acceptance path: artifact bytes over the wire, digest back,
    predictions against the digest — client knows nothing of repro."""
    app, h = _app_and_server()
    with app, h:
        art = maclaurin.compile(_svm(4))
        payload = base64.b64encode(art.to_bytes()).decode()
        c = _Client(h.host, h.port)
        status, body, _ = c.request(
            "POST", "/v1/models",
            {"artifact_b64": payload, "spec": {"alias": "uploaded"}})
        assert status == 201
        digest = body["digest"]
        assert digest == art.digest()      # content addressing end to end
        status, listing, _ = c.request("GET", "/v1/models")
        assert status == 200
        assert [m["digest"] for m in listing["models"]] == [digest]
        assert listing["models"][0]["aliases"] == ["uploaded"]
        status, body, _ = c.request(
            "POST", "/v1/models/uploaded:predict",
            {"rows": _rows(np.random.default_rng(1), 3)})
        assert status == 200 and body["digest"] == digest
        # a corrupt upload is refused with the taxonomy, never indexed
        bad = base64.b64encode(art.to_bytes()[:100]).decode()
        status, body, _ = c.request(
            "POST", "/v1/models", {"artifact_b64": bad, "spec": {}})
        assert status == 503
        assert body["error"]["code"] == "artifact_corrupt"
        c.close()


# ------------------------------------------------------------- coalescing


def test_concurrent_clients_coalesce_into_shared_flushes():
    # a wide flush window so a burst of HTTP requests lands in ONE
    # coalescing window; each client sends 1 row, the engine's
    # min_bucket is 8 — shared flushes are the only way this stays
    # under requests/2 flushes
    app, h = _app_and_server(max_wait_us=100_000.0)
    with app, h:
        digest = _publish(app, _svm(0), "det")
        warm = _Client(h.host, h.port)
        warm.request("POST", "/v1/models/det:predict",
                     {"rows": _rows(np.random.default_rng(0), 2)})
        warm.close()
        n_clients = 12
        barrier = threading.Barrier(n_clients)
        results = [None] * n_clients

        def worker(i):
            c = _Client(h.host, h.port)
            rows = _rows(np.random.default_rng(100 + i), 1)
            barrier.wait()
            results[i] = c.request(
                "POST", "/v1/models/det:predict", {"rows": rows})
            c.close()

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(r[0] == 200 for r in results)
        st = app.runtime.stats("det")
        burst_flushes = st["flushes"] - 1            # minus the warmup flush
        assert st["requests"] == n_clients + 1
        assert burst_flushes <= n_clients // 2, st["flushes"]
        assert st["served_requests"] == n_clients + 1
        # nothing hangs behind the async bridge, and every request the
        # wire admitted is accounted for
        assert st["queue_rows"] == 0
        cons = app.runtime.obs.tracer.conservation(digest[:12])
        assert cons["unaccounted"] == 0, cons
        assert cons["submitted"] == cons["admitted"] + cons["shed"] == n_clients + 1


# ------------------------------------------------- overload + Retry-After


def test_overload_returns_429_with_parseable_retry_after():
    fi = FaultInjector(0, slow_step_rate=1.0, slow_step_s=0.05)
    rt = Runtime(engine_opts=ENGINE_OPTS, warmup_on_load=False,
                 fault_injector=fi, max_queue_rows=16, max_wait_us=100.0)
    app = create_app(rt)
    with rt, app, serve(app) as h:
        _publish(app, _svm(1), "det")
        warm = _Client(h.host, h.port)
        warm.request("POST", "/v1/models/det:predict",
                     {"rows": _rows(np.random.default_rng(0), 2)})
        n_clients, per_client = 10, 6
        outcomes = []
        lock = threading.Lock()

        def worker(i):
            c = _Client(h.host, h.port)
            rng = np.random.default_rng(200 + i)
            for _ in range(per_client):
                status, body, headers = c.request(
                    "POST", "/v1/models/det:predict",
                    {"rows": _rows(rng, 4)})
                with lock:
                    outcomes.append((status, body, headers))
            c.close()

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        ok = [o for o in outcomes if o[0] == 200]
        shed = [o for o in outcomes if o[0] == 429]
        assert len(ok) + len(shed) == n_clients * per_client
        assert shed, "burst never overloaded the bounded queue"
        for status, body, headers in shed:
            retry = headers.get("retry-after")
            assert retry is not None and int(retry) >= 1    # parseable, RFC
            assert body["error"]["code"] == "overloaded"
            assert body["error"]["retry_after_s"] > 0.0
        # client-observed sheds match the runtime's own accounting
        st = rt.stats("det")
        assert st["shed_requests"] == len(shed)
        digest = st["digest"]
        cons = rt.obs.tracer.conservation(digest[:12])
        assert cons["unaccounted"] == 0, cons
        assert cons["shed"] == len(shed)
        assert cons["served"] == len(ok) + 1                 # + warmup
        warm.close()


def test_deadline_maps_to_504():
    fi = FaultInjector(0, slow_step_rate=1.0, slow_step_s=0.25)
    rt = Runtime(engine_opts=ENGINE_OPTS, warmup_on_load=False,
                 fault_injector=fi, max_wait_us=100.0)
    app = create_app(rt)
    with rt, app, serve(app) as h:
        _publish(app, _svm(1), "det")
        c = _Client(h.host, h.port)
        c.request("POST", "/v1/models/det:predict",
                  {"rows": _rows(np.random.default_rng(0), 2)})   # warm

        # occupy the engine with a slow flush so the deadline request
        # expires IN QUEUE (deadlines bound queue wait, not service)
        def occupy():
            blocker = _Client(h.host, h.port)
            blocker.request("POST", "/v1/models/det:predict",
                            {"rows": _rows(np.random.default_rng(2), 2)})
            blocker.close()

        t = threading.Thread(target=occupy)
        t.start()
        time.sleep(0.05)                      # blocker's flush is in service
        status, body, _ = c.request(
            "POST", "/v1/models/det:predict",
            {"rows": _rows(np.random.default_rng(1), 2),
             "deadline_s": 0.05})
        t.join()
        assert status == 504
        assert body["error"]["code"] == "deadline_exceeded"
        c.close()


# ----------------------------------------------------------------- tenancy


def test_tenant_quota_sheds_conserve_across_all_layers():
    # rate 1e-6 rps with burst 3: exactly 3 admits, then sheds for the
    # next ~11 days — deterministic without clock injection
    tenants = [
        TenantConfig(name="acme", api_key="k-acme",
                     rate_rps=1e-6, burst=3),
        TenantConfig(name="umbrella", api_key="k-umb",
                     rows_per_s=1e-6, row_burst=8),
    ]
    app, h = _app_and_server(tenants=tenants)
    with app, h:
        digest = _publish(app, _svm(0), "det")
        c = _Client(h.host, h.port)
        rng = np.random.default_rng(0)

        # no key / bad key → 401 before anything is accounted
        status, body, _ = c.request(
            "POST", "/v1/models/det:predict", {"rows": _rows(rng, 1)})
        assert status == 401 and body["error"]["code"] == "unauthenticated"
        status, _, _ = c.request(
            "POST", "/v1/models/det:predict", {"rows": _rows(rng, 1)},
            headers={"x-api-key": "wrong"})
        assert status == 401

        # acme: 3 request tokens, then request-rate sheds
        acme_ok = acme_shed = 0
        for _ in range(7):
            status, body, headers = c.request(
                "POST", "/v1/models/det:predict", {"rows": _rows(rng, 2)},
                headers={"x-api-key": "k-acme"})
            if status == 200:
                acme_ok += 1
            else:
                acme_shed += 1
                assert status == 429
                assert body["error"]["code"] == "tenant_quota"
                assert body["error"]["tenant"] == "acme"
                assert body["error"]["quota"] == "rate_rps"
                assert int(headers["retry-after"]) >= 1
        assert (acme_ok, acme_shed) == (3, 4)

        # umbrella: 8 row tokens → a 5-row then a 3-row pass, then shed
        umb_ok = umb_shed = 0
        for n in (5, 3, 2, 2):
            status, body, _ = c.request(
                "POST", "/v1/models/det:predict", {"rows": _rows(rng, n)},
                headers={"x-api-key": "k-umb"})
            if status == 200:
                umb_ok += 1
            else:
                umb_shed += 1
                assert body["error"]["quota"] == "rows_per_s"
        assert (umb_ok, umb_shed) == (2, 2)

        # three-way conservation: client == telemetry == spans
        client_shed = acme_shed + umb_shed
        client_ok = acme_ok + umb_ok
        st = app.runtime.stats("det")
        assert st["shed_requests"] == client_shed
        assert st["served_requests"] == client_ok
        cons = app.runtime.obs.tracer.conservation(digest[:12])
        assert cons["unaccounted"] == 0, cons
        assert cons["shed"] == client_shed
        assert cons["served"] == client_ok
        assert cons["submitted"] == client_ok + client_shed
        # the shed spans name the tenant and the quota
        sheds = app.runtime.obs.tracer.spans(digest[:12], "request.shed")
        assert sorted(s["attrs"]["tenant"] for s in sheds) == sorted(
            ["acme"] * acme_shed + ["umbrella"] * umb_shed)
        assert all(s["attrs"]["reason"] == "tenant_quota" for s in sheds)
        # per-tenant accounting agrees with the client too
        status, tsnap, _ = c.request("GET", "/v1/tenants")
        by_name = {t["name"]: t for t in tsnap["tenants"]}
        assert by_name["acme"]["shed"] == acme_shed
        assert by_name["acme"]["admitted"] == acme_ok
        assert by_name["umbrella"]["shed_rows"] == 4
        c.close()


def test_tenant_max_rows_is_a_400_not_a_shed():
    tenants = [TenantConfig(name="t", api_key="k", max_rows=4)]
    app, h = _app_and_server(tenants=tenants)
    with app, h:
        _publish(app, _svm(0), "det")
        c = _Client(h.host, h.port)
        status, body, _ = c.request(
            "POST", "/v1/models/det:predict",
            {"rows": _rows(np.random.default_rng(0), 5)},
            headers={"x-api-key": "k"})
        assert status == 400
        assert body["error"]["code"] == "invalid_request"
        assert app.runtime.stats("det")["shed_requests"] == 0
        c.close()


# --------------------------------------------------------------- hot swap


def test_alias_hot_swap_mid_traffic_routes_new_requests():
    app, h = _app_and_server(max_wait_us=500.0)
    with app, h:
        m = _svm(0)
        d1 = _publish(app, m, "det", family=maclaurin)
        art2 = fourier.compile(m)
        stop = threading.Event()
        seen, errors = [], []
        lock = threading.Lock()

        def traffic(i):
            c = _Client(h.host, h.port)
            rng = np.random.default_rng(300 + i)
            while not stop.is_set():
                status, body, _ = c.request(
                    "POST", "/v1/models/det:predict", {"rows": _rows(rng, 2)})
                with lock:
                    if status == 200:
                        seen.append(body["digest"])
                    else:
                        errors.append((status, body))
            c.close()

        threads = [threading.Thread(target=traffic, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        admin = _Client(h.host, h.port)

        def wait_for(count, timeout=60.0):
            t0 = time.monotonic()
            while time.monotonic() - t0 < timeout:
                with lock:
                    if len(seen) >= count or errors:
                        return
                time.sleep(0.005)
            raise AssertionError(f"traffic stalled below {count} responses")

        wait_for(8)                           # live old-digest traffic first
        payload = base64.b64encode(art2.to_bytes()).decode()
        status, body, _ = admin.request(
            "POST", "/v1/models",
            {"artifact_b64": payload, "spec": {"alias": "det"}})
        assert status == 201
        d2 = body["digest"]
        assert d2 != d1
        # every NEW request routes to the new digest
        with lock:
            after_flip = len(seen)
        wait_for(after_flip + 8)
        stop.set()
        for t in threads:
            t.join()
        assert not errors, errors[:3]
        assert set(seen) == {d1, d2}          # both digests served, no third
        tail = seen[-4:]
        assert all(d == d2 for d in tail), "new requests still on old digest"
        admin.close()


# -------------------------------------------------------------- management


def test_evict_replicas_and_stats_routes():
    app, h = _app_and_server()
    with app, h:
        digest = _publish(app, _svm(0), "det")
        c = _Client(h.host, h.port)
        rng = np.random.default_rng(0)
        c.request("POST", "/v1/models/det:predict", {"rows": _rows(rng, 2)})

        status, body, _ = c.request("POST", "/v1/models/det:replicas",
                                    {"replicas": 2})
        assert status == 200 and body == {"digest": digest, "replicas": 2}
        status, body, _ = c.request(
            "POST", "/v1/models/det:predict", {"rows": _rows(rng, 2)})
        assert status == 200                   # rescale is a live operation

        status, body, _ = c.request("POST", "/v1/models/det:evict", None)
        assert status == 200 and body["evicted"]
        status, listing, _ = c.request("GET", "/v1/models")
        assert listing["models"][0]["loaded"] is False
        status, body, _ = c.request(
            "POST", "/v1/models/det:predict", {"rows": _rows(rng, 2)})
        assert status == 200                   # transparent rebuild

        status, body, _ = c.request("POST", "/v1/models/det:alias",
                                    {"alias": "prod"})
        assert status == 200 and body["digest"] == digest
        status, st, _ = c.request("GET", "/v1/models/det/stats")
        assert status == 200 and st["digest"] == digest
        assert st["served_requests"] >= 3
        status, st, _ = c.request("GET", "/v1/stats")
        assert status == 200 and digest[:12] in st["models"]
        c.close()


# ----------------------------------------------------------------- metrics


_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [^ ]+$"
)


def test_metrics_endpoint_parses_as_prometheus_text():
    app, h = _app_and_server()
    with app, h:
        _publish(app, _svm(0), "det")
        c = _Client(h.host, h.port)
        c.request("POST", "/v1/models/det:predict",
                  {"rows": _rows(np.random.default_rng(0), 3)})
        status, raw, headers = c.request("GET", "/metrics")
        assert status == 200
        assert headers["content-type"].startswith("text/plain")
        text = raw.decode() if isinstance(raw, bytes) else raw
        assert text == app.runtime.render_prometheus()   # served VERBATIM
        names = set()
        for line in text.strip().splitlines():
            if line.startswith("# HELP") or line.startswith("# TYPE"):
                assert len(line.split(None, 3)) >= 3
                continue
            assert _PROM_LINE.match(line), line
            names.add(line.split("{")[0].split(" ")[0])
        assert any(n.startswith("repro_serve_") for n in names)
        c.close()
