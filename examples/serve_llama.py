"""Serve a (tiny) Llama behind the streaming serving front door.

Features on display: a 2-replica :class:`ReplicaSet` of continuous-batching
paged-KV engines (chunked prefill, int8 KV pages, decode blocks sized to
the measured dispatch latency), prefix-affinity routing, SLO-aware
admission, and the stdlib SSE gateway -- the script starts the HTTP front
door, drives it with a few clients (streaming and non-streaming), and
prints what came back.

Run:  JAX_PLATFORMS=cpu python examples/serve_llama.py

Set METRICS_PORT to also expose engine + frontend telemetry on a
Prometheus pull endpoint for the duration of the run (e.g.
METRICS_PORT=9400 -> scrape http://127.0.0.1:9400/metrics; 0 lets the OS
pick a port).  The gateway itself always serves /metrics too.

Set JOURNAL_DIR to turn on the durable request plane: requests journal to
that directory before acknowledgment, submits become idempotent
(Idempotency-Key header), SSE streams resumable (Last-Event-ID), and a
restarted gateway pointed at the same directory recovers unfinished
requests -- the script demonstrates an idempotent replay when the knob is
set.
"""
import os
import sys

# runnable from any cwd: the repo root (one level up) on sys.path
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.core.compile_cache import enable_compile_cache
from paddle_tpu import observability as obs
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.inference.serving import LLMEngine
from paddle_tpu.inference.frontend import (
    ReplicaSet, SLOAdmission, start_gateway, http_completion)


def main():
    enable_compile_cache()
    paddle.seed(0)
    metrics = None
    if os.environ.get("METRICS_PORT") is not None:
        obs.enable()
        metrics = obs.start_metrics_server(
            port=int(os.environ["METRICS_PORT"]))
        print(f"metrics endpoint: {metrics.url}")
    model = LlamaForCausalLM(LlamaConfig.tiny())
    model.eval()

    def _engine():
        return LLMEngine(model, max_batch=2, max_len=96, page_size=8,
                         prefill_chunk=16, decode_block="auto",
                         kv_cache_dtype="int8", prefix_cache=True)

    rng = np.random.RandomState(0)
    with ReplicaSet([_engine(), _engine()],
                    admission=SLOAdmission(max_queue_per_replica=32)) as rs:
        journal_dir = os.environ.get("JOURNAL_DIR")
        gw = start_gateway(rs, port=int(os.environ.get("PORT", 0)),
                           journal_dir=journal_dir)
        print(f"front door: {gw.url}/v1/completions"
              + (f" (journal: {journal_dir})" if journal_dir else ""))
        try:
            shared = rng.randint(
                1, model.config.vocab_size, (12,)).tolist()
            # one streaming client: tokens arrive as SSE events
            out = http_completion(gw.url, shared, max_tokens=16,
                                  stream=True)
            print(f"stream: {len(out['tokens'])} tokens over "
                  f"{out['events']} SSE events ({out['status']}) "
                  f"-> {out['tokens'][:8]}...")
            # a few non-streaming clients sharing the same prompt prefix,
            # so the router can exploit the replicas' prefix caches
            for i in range(3):
                prompt = shared + rng.randint(
                    1, model.config.vocab_size, (4,)).tolist()
                out = http_completion(
                    gw.url, prompt, max_tokens=16, do_sample=bool(i),
                    temperature=0.8, top_p=0.9, seed=7)
                print(f"request {i}: {len(out['tokens'])} tokens on "
                      f"{out.get('replica', 'durable')} ({out['status']}) "
                      f"-> {out['tokens'][:8]}...")
            if journal_dir is not None:
                # idempotent replay: same key, same tokens, nothing re-runs
                first = http_completion(
                    gw.url, shared, max_tokens=16,
                    headers={"Idempotency-Key": "demo"})
                again = http_completion(
                    gw.url, shared, max_tokens=16,
                    headers={"Idempotency-Key": "demo"})
                print(f"idempotent replay: "
                      f"{'match' if again['tokens'] == first['tokens'] else 'MISMATCH'}"
                      f" ({len(again['tokens'])} tokens, key="
                      f"{again['idempotency_key']})")
                print(f"journal: {gw.plane.health()}")
            for name, h in rs.health().items():
                print(f"replica {name}: finished={h['finished']} "
                      f"free_pages={h['free_pages']} alive={h['alive']}")
        finally:
            gw.close()
    if metrics is not None:
        lines = [ln for ln in obs.render_prometheus().splitlines()
                 if ln.startswith(("serving_ttft_seconds_count",
                                   "frontend_requests_total",
                                   "frontend_routed_total"))]
        print("scraped:", *lines, sep="\n  ")
        metrics.close()
        obs.disable()


if __name__ == "__main__":
    main()
