"""Streaming serving example: per-request token callbacks and the
generator API over the continuous-batching engine.

Two ways to consume tokens before the run drains:

* ``Request.on_token`` — a per-request callback that fires with each of
  that request's :class:`~repro.launch.engine.TokenEvent`\\ s as the
  scheduler commits them (time-to-first-token lands in
  ``last_stats.ttft_p50_ms`` / ``ttft_p99_ms``);
* ``Engine.stream(reqs)`` — one generator over *all* requests' events in
  commit order; each terminal event carries its Completion.

    PYTHONPATH=src python examples/serve_stream.py --arch qwen2.5-14b
    PYTHONPATH=src python examples/serve_stream.py --mesh 8 --slots 8
"""
import argparse
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--mode", default="xla",
                    choices=["brainslug", "xla", "barrier"])
    ap.add_argument("--requests", type=int, default=5)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=10)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--mesh", type=int, default=0,
                    help="force N host devices and serve through a "
                         "shard_map mesh (0 = single device)")
    args = ap.parse_args()

    if args.mesh:
        # must run before jax initializes its backend
        flag = f"--xla_force_host_platform_device_count={args.mesh}"
        if flag not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " " + flag).strip()

    import numpy as np

    from repro.launch import compile_cache, mesh as mesh_mod
    from repro.launch.engine import Request
    from repro.launch.serve import ServeConfig, Server

    compile_cache.configure()
    sc = ServeConfig(arch=args.arch, mode=args.mode, batch=args.slots,
                     prompt_len=args.prompt_len, new_tokens=args.new_tokens,
                     max_len=args.prompt_len + args.new_tokens + 1)
    server = Server(sc)
    rng = np.random.default_rng(0)

    def make_reqs(cb=None):
        reqs = []
        for i in range(args.requests):
            plen = int(rng.integers(1, sc.prompt_len + 1))
            reqs.append(Request(
                request_id=i,
                prompt=rng.integers(0, server.cfg.vocab_size,
                                    (plen,)).astype(np.int32),
                max_new_tokens=int(rng.integers(2, sc.new_tokens + 1)),
                on_token=cb))
        return reqs

    mesh = mesh_mod.make_test_mesh(args.mesh) if args.mesh else None
    engine = server.engine(slots=args.slots, mesh=mesh)

    # --- per-request callbacks ---------------------------------------------
    t0 = time.time()

    def cb(ev):
        if ev.done:
            print(f"  [{time.time() - t0:5.2f}s] request {ev.request_id} "
                  f"done: {ev.completion.tokens.tolist()}")
        elif ev.index == 0:
            print(f"  [{time.time() - t0:5.2f}s] request {ev.request_id} "
                  f"first token {ev.token}")

    completions = engine.run(make_reqs(cb))
    s = engine.last_stats
    print(f"[callbacks] {s.completed} completions, "
          f"ttft p50 {s.ttft_p50_ms:.1f}ms p99 {s.ttft_p99_ms:.1f}ms")

    del completions

    # --- generator ---------------------------------------------------------
    n_tok, done = 0, []
    for ev in engine.stream(make_reqs()):
        if ev.done:
            done.append(ev.completion)
        else:
            n_tok += 1
    assert n_tok == sum(len(c.tokens) for c in done)
    print(f"[generator] streamed {n_tok} tokens across {len(done)} "
          f"completions")
    rep = engine.report()
    print(f"[report] decode_path={rep['decode_path']} "
          f"mesh={rep['mesh_axes'] or 'single-device'}")


if __name__ == "__main__":
    sys.exit(main())
