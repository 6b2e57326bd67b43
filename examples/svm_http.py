"""HTTP front door demo: the runtime as a network service.

Everything here talks to the server the way a real client would —
``http.client`` over a localhost socket, JSON bodies, an ``x-api-key``
header — with zero ``repro`` imports on the client side of the wire.
Four acts:

1. **Publish over the wire** — POST base64 artifact bytes to
   ``/v1/models``; the server spools, validates, content-addresses and
   aliases them exactly like a local ``add_file``.

2. **Coalesced predictions** — a burst of concurrent HTTP clients
   shares ``MicroBatcher`` flushes (the async bridge preserves
   deferred sync), and every response row carries the paper's §4
   validity verdict plus the serving digest.

3. **Typed refusals** — on a tenanted, deliberately-slow server:
   missing key ⇒ 401 ``unauthenticated``; a tenant over its bucket ⇒
   429 ``tenant_quota`` with a parseable ``Retry-After``; a full
   runtime queue ⇒ 429 ``overloaded``. Every shed — tenant or queue —
   lands in the SAME conservation identity, checkable over HTTP.

4. **Metrics scrape** — ``GET /metrics`` serves the runtime's
   Prometheus exposition verbatim.

    PYTHONPATH=src python examples/svm_http.py
"""

import base64
import concurrent.futures
import http.client
import json
from urllib.parse import urlparse

import numpy as np
import jax.numpy as jnp

from repro.core import gamma_max
from repro.core.families import maclaurin
from repro.core.rbf import SVMModel
from repro.serve import FaultInjector, Runtime
from repro.serve.server import TenantConfig, create_app, serve

DIM = 16
BURST_CLIENTS = 8
BURST_REQS = 6
REQ_ROWS = 4


def make_model(seed=0, d=DIM, n_sv=64):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n_sv, d)).astype(np.float32) * 0.5
    gamma = 0.8 * float(gamma_max(jnp.asarray(X)))
    ay = rng.standard_normal(n_sv).astype(np.float32) * 0.5
    return SVMModel(
        X=jnp.asarray(X),
        alpha_y=jnp.asarray(ay),
        b=jnp.float32(0.1),
        gamma=jnp.float32(gamma),
    )


class Client:
    """A thin JSON-over-HTTP client — stdlib only, no repro imports."""

    def __init__(self, url):
        u = urlparse(url)
        self.conn = http.client.HTTPConnection(u.hostname, u.port, timeout=30)

    def request(self, method, path, body=None, key=None):
        headers = {"content-type": "application/json"}
        if key:
            headers["x-api-key"] = key
        payload = json.dumps(body).encode() if body is not None else None
        self.conn.request(method, path, body=payload, headers=headers)
        resp = self.conn.getresponse()
        raw = resp.read()
        hdrs = {k.lower(): v for k, v in resp.getheaders()}
        ctype = hdrs.get("content-type", "")
        data = json.loads(raw) if ctype.startswith("application/json") else raw
        return resp.status, hdrs, data


def act_1_and_2_and_4(art):
    app = create_app(
        max_wait_us=100_000.0,  # generous window: let the burst coalesce
        engine_opts=dict(min_bucket=8, max_batch=64),
        warmup_on_load=False,
    )
    handle = serve(app)
    c = Client(handle.url)
    try:
        # ---- act 1: publish over the wire --------------------------------
        payload = base64.b64encode(art.to_bytes()).decode()
        status, _, out = c.request(
            "POST", "/v1/models",
            {"artifact_b64": payload, "spec": {"alias": "det"}},
        )
        digest = out["digest"]
        print(f"[publish] POST /v1/models -> {status}, digest {digest[:12]} "
              f"(content-addressed: digest == sha256 of the bytes)")
        assert digest == art.digest()
        _, _, listing = c.request("GET", "/v1/models")
        row = listing["models"][0]
        print(f"[publish] GET /v1/models -> aliases={row['aliases']} "
              f"loaded={row['loaded']} nbytes={row['nbytes']}")

        # ---- act 2: a coalesced burst with §4 verdicts -------------------
        before = app.runtime.stats("det")

        def burst(i):
            cc = Client(handle.url)
            got = []
            r = np.random.default_rng(100 + i)
            for _ in range(BURST_REQS):
                rows = (0.3 * r.standard_normal((REQ_ROWS, DIM))).tolist()
                s, _, o = cc.request(
                    "POST", "/v1/models/det:predict", {"rows": rows}
                )
                assert s == 200, o
                got.append(o)
            return got

        with concurrent.futures.ThreadPoolExecutor(BURST_CLIENTS) as pool:
            outs = [o for f in [pool.submit(burst, i)
                                for i in range(BURST_CLIENTS)]
                    for o in f.result()]
        n_rows = sum(o["n"] for o in outs)
        n_valid = sum(sum(o["valid"]) for o in outs)
        after = app.runtime.stats("det")
        flushes = after["flushes"] - before["flushes"]
        print(f"[predict] {len(outs)} HTTP requests ({n_rows} rows) from "
              f"{BURST_CLIENTS} clients -> {flushes} engine flushes "
              f"(coalescing {len(outs) / max(1, flushes):.1f}x)")
        print(f"[predict] §4 validity over the wire: {n_valid}/{n_rows} rows "
              f"fast-path valid; every response pinned digest "
              f"{outs[0]['digest'][:12]}")

        # ---- act 4: Prometheus scrape ------------------------------------
        status, hdrs, text = c.request("GET", "/metrics")
        lines = text.decode().splitlines()
        picked = [ln for ln in lines
                  if ln.startswith(("repro_serve_requests_total",
                                    "repro_serve_validity_fraction"))]
        print(f"[metrics] GET /metrics -> {status} "
              f"({hdrs['content-type'].split(';')[0]}, {len(lines)} lines):")
        for ln in picked[:4]:
            print(f"  {ln}")
    finally:
        handle.close()
        app.close()


def act_3_typed_refusals(art):
    # a deliberately slow engine (every flush pinned at 50 ms) behind a
    # small admission bound, plus one tenant whose request bucket holds
    # exactly 3 tokens and refills ~never
    fi = FaultInjector(seed=0, slow_step_rate=1.0, slow_step_s=0.05)
    app = create_app(
        max_wait_us=100.0,
        max_queue_rows=16,
        engine_opts=dict(min_bucket=8, max_batch=64),
        warmup_on_load=False,
        fault_injector=fi,
        tenants=[
            TenantConfig("acme", api_key="acme-key",
                         rate_rps=1e-6, burst=3),
            TenantConfig("umbrella", api_key="umbrella-key"),
        ],
    )
    handle = serve(app)
    try:
        c = Client(handle.url)
        payload = base64.b64encode(art.to_bytes()).decode()
        _, _, out = c.request(
            "POST", "/v1/models",
            {"artifact_b64": payload, "spec": {"alias": "det"}},
        )
        digest = out["digest"]
        rows = [[0.0] * DIM]

        status, _, body = c.request("POST", "/v1/models/det:predict",
                                    {"rows": rows})
        print(f"[refusals] no api key        -> {status} "
              f"{body['error']['code']}")

        verdicts = []
        for _ in range(6):
            status, hdrs, body = c.request(
                "POST", "/v1/models/det:predict", {"rows": rows},
                key="acme-key",
            )
            verdicts.append(
                (status, body.get("error", {}).get("code"),
                 hdrs.get("retry-after"))
            )
        ok = sum(1 for s, _, _ in verdicts if s == 200)
        s, code, retry = verdicts[-1]
        print(f"[refusals] tenant 'acme' (burst=3): {ok} admitted, then "
              f"{s} {code} with Retry-After: {retry}s")

        def flood(i):
            cc = Client(handle.url)
            r = np.random.default_rng(i)
            hits = []
            for _ in range(BURST_REQS):
                rw = (0.3 * r.standard_normal((REQ_ROWS, DIM))).tolist()
                s, h, o = cc.request(
                    "POST", "/v1/models/det:predict", {"rows": rw},
                    key="umbrella-key",
                )
                hits.append((s, o.get("error", {}).get("code"),
                             h.get("retry-after")))
            return hits

        with concurrent.futures.ThreadPoolExecutor(BURST_CLIENTS) as pool:
            hits = [h for f in [pool.submit(flood, i)
                                for i in range(BURST_CLIENTS)]
                    for h in f.result()]
        served = sum(1 for s, _, _ in hits if s == 200)
        shed = [h for h in hits if h[0] == 429]
        print(f"[refusals] unlimited tenant vs 50 ms flushes + "
              f"max_queue_rows=16: {served} served, {len(shed)} shed "
              f"{shed[0][1]} (Retry-After: {shed[0][2]}s)" if shed else
              f"[refusals] {served} served, no sheds (machine too fast)")

        # conservation holds ACROSS the network hop: the client's own 2xx/
        # 429 tally, the runtime's telemetry, and the span counters agree
        st = app.runtime.stats(digest)
        tenant_shed = sum(1 for s, code, _ in hits + verdicts
                          if s == 429 and code == "tenant_quota")
        _, _, tsnap = c.request("GET", "/v1/tenants")
        acme = next(t for t in tsnap["tenants"] if t["name"] == "acme")
        cons = (app.runtime.obs.tracer.conservation(digest[:12])
                if app.runtime.obs is not None else {})
        print(f"[conserve] client saw {served + ok} ok / "
              f"{len(shed) + (6 - ok)} shed; telemetry "
              f"served={st['served_requests']} shed={st['shed_requests']}; "
              f"spans unaccounted={cons.get('unaccounted')}")
        print(f"[conserve] GET /v1/tenants: acme admitted={acme['admitted']} "
              f"shed={acme['shed']} (tenant sheds: {tenant_shed})")
        assert cons.get("unaccounted", 0) == 0
        assert st["shed_requests"] == len(shed) + (6 - ok)
    finally:
        handle.close()
        app.close()


def main():
    art = maclaurin.compile(make_model())
    act_1_and_2_and_4(art)
    act_3_typed_refusals(art)


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
