"""Serve a model over HTTP: the async gateway on a real socket
(DESIGN.md §13).

Starts the OpenAI-compatible gateway over a smoke-scale ``repro.Session``,
then exercises it the way an external client would:

1. ``GET /v1/models`` + ``GET /healthz`` via stdlib ``urllib``;
2. a **streaming** chat completion over a raw asyncio connection, printing
   each SSE delta with its per-token wire latency as it arrives;
3. a **non-streaming** completion via ``urllib`` (blocking HTTP, run in a
   worker thread) — same tokens, one JSON body;
4. a mid-serve ``POST /admin/rebudget`` while a second stream is in
   flight: the schedule re-plans live and the stream finishes unperturbed.

    PYTHONPATH=src python examples/serve_http.py [--arch qwen2-0.5b]
"""
import argparse
import asyncio
import json
import os
import time
import urllib.request

# pin per-op bf16 rounding (see tests/conftest.py) so the rebudget
# comparison below is token-exact across schedules
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_allow_excess_precision" not in _flags:
    os.environ["XLA_FLAGS"] = \
        (_flags + " --xla_allow_excess_precision=false").strip()

from repro import Session                               # noqa: E402
from repro.compile_cache import enable_compile_cache    # noqa: E402
from repro.configs import get_smoke_config, list_archs  # noqa: E402
from repro.core import CLI2, InferenceSetting, build_graph  # noqa: E402
from repro.gateway.sse import iter_events               # noqa: E402


def http_json(base, path, payload=None, timeout=60):
    """Blocking stdlib request; call via ``asyncio.to_thread``."""
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(base + path, data=data,
                                 headers={"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


async def stream_chat(host, port, body, tag):
    """Raw-socket SSE client: prints every delta with its wire latency."""
    reader, writer = await asyncio.open_connection(host, port)
    payload = json.dumps(body).encode()
    writer.write((f"POST /v1/chat/completions HTTP/1.1\r\n"
                  f"host: {host}\r\ncontent-length: {len(payload)}\r\n"
                  f"\r\n").encode() + payload)
    await writer.drain()
    head = await reader.readuntil(b"\r\n\r\n")
    assert b"200" in head.split(b"\r\n", 1)[0], head
    tokens, t_prev = [], time.perf_counter()
    while True:
        block = await reader.readuntil(b"\n\n")
        now = time.perf_counter()
        for ev in iter_events(block):
            if ev == "[DONE]":
                writer.close()
                await writer.wait_closed()
                return tokens
            delta = json.loads(ev)["choices"][0]["delta"]
            tokens.append(delta["token_id"])
            print(f"    [{tag}] token {delta['token_id']:>5}  "
                  f"(+{(now - t_prev) * 1e3:6.1f} ms)")
        t_prev = now


async def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b",
                    choices=list_archs(include_paper=True))
    ap.add_argument("--port", type=int, default=8377)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    total = sum(s.weight_bytes for s in build_graph(cfg, wdtype=2))
    sess = Session.open(cfg, CLI2, int(total * 0.2) + 1,
                        InferenceSetting(batch=2, context=128), max_seq=128)
    gw = sess.gateway(max_batch=2, max_queue=16)
    server = asyncio.ensure_future(gw.serve_forever("127.0.0.1", args.port))
    while not hasattr(gw, "bound_address"):
        await asyncio.sleep(0.01)
    host, port = gw.bound_address
    base = f"http://{host}:{port}"
    print(f"[1] gateway listening on {base}")

    models = await asyncio.to_thread(http_json, base, "/v1/models")
    health = await asyncio.to_thread(http_json, base, "/healthz")
    print(f"    models: {[m['id'] for m in models['data']]}, "
          f"health: {health['status']}")

    print("[2] streaming completion (SSE, per-token wire latency):")
    toks_stream = await stream_chat(host, port, {
        "model": cfg.name, "token_ids": [11, 29, 3, 7],
        "max_tokens": 6, "stream": True}, tag="stream")

    print("[3] same prompt, non-streaming (urllib in a worker thread):")
    resp = await asyncio.to_thread(http_json, base, "/v1/chat/completions", {
        "model": cfg.name, "token_ids": [11, 29, 3, 7], "max_tokens": 6})
    choice = resp["choices"][0]
    print(f"    content: {choice['message']['content']!r}  "
          f"usage: {resp['usage']}")
    assert choice["token_ids"] == toks_stream, "stream/unary diverged"
    print("    stream and unary token-identical: OK")

    print("[4] rebudget to 50% mid-stream (live re-plan over the wire):")
    in_flight = asyncio.ensure_future(stream_chat(host, port, {
        "model": cfg.name, "token_ids": [5, 6, 7], "max_tokens": 6,
        "stream": True}, tag="inflight"))
    await asyncio.sleep(0.05)
    re = await asyncio.to_thread(http_json, base, "/admin/rebudget",
                                 {"budget_bytes": int(total * 0.5) + 1})
    print(f"    rebudget applied: {re['summary']}")
    toks_inflight = await in_flight
    baseline = await asyncio.to_thread(http_json, base,
                                       "/v1/chat/completions",
                                       {"model": cfg.name,
                                        "token_ids": [5, 6, 7],
                                        "max_tokens": 6})
    assert baseline["choices"][0]["token_ids"] == toks_inflight, \
        "rebudget changed tokens"
    print("    in-flight stream token-identical across the swap: OK")

    m = await asyncio.to_thread(http_json, base, "/metrics")
    led = m["broker"]["ledger"]
    print(f"[5] /metrics: completed={led['completed']} "
          f"reconciles={m['broker']['reconciles']} "
          f"ttft_p50={m['ttft_p50_s'] * 1e3:.0f}ms")
    server.cancel()
    await gw.close(drain=False)
    print("done.")


if __name__ == "__main__":
    asyncio.run(main())
