"""Serving cells: closed-loop static batches through the program's prefill
and decode.

Set-up makes the weights on the device from the seed with the program's
``tree_init``, compiles ``harness.prefill`` and ``harness.decode`` ahead
of time as ``launch.serve.serve`` does (a cache of prompt + generated + 8
positions), with the cache donated: each step writes it in place, so the
steps queued ahead hold no cache of their own (``serve`` donates none,
and each step there allocates a whole new cache).  Set-up warms the
programs with a short batch through the window's own loop.  The window
then serves batch after batch, back to back: the prompts go to the
device, prefill writes the cache, and every token is sampled greedily on
the device, fed to the next decode step there and copied to the host as
soon as it is made.  The host reads the tokens in order and stamps each
arrival.  Work is dispatched ``ahead_steps`` token steps (a prefill
counts as one) beyond the token being read: enough to keep the chip fed through a short stall of the
host, and few enough that a dispatch never waits on the runtime, which
holds one back while too many programs are in flight; the reads would
then all come late by the same amount, and a time to first token would
read one decode step.  A batch that starts in the window is finished;
when the window's time is up nothing new starts, all that was sent is
read, and the window closes after that wait.

On the host clock: a request's time to first token runs from its batch's
start (the later of the moment its prompts were sent and the moment the
batch before it delivered its last token) to its first token; the gaps
between its tokens are those between their arrivals.

After the window the program's state is freed and a sample of the
finished requests, drawn from the seed, is run through the float32
reference over its prompt and served tokens.
"""

from __future__ import annotations

import collections
import gc
import time

import numpy as np

import compare
import program
import traffic as traffic_gen
from reference import dense_lm


def run(cell) -> "compare.Outcome":
    import jax
    import jax.numpy as jnp

    from repro.models.api import ShapeCell
    from repro.models.layers import Runtime
    from repro.models.param import tree_init

    model, tr = cell.model, cell.traffic
    B, P, G, V = tr["batch"], tr["prompt_len"], tr["gen_len"], model["vocab_size"]
    h = program.harness(model)
    rt = Runtime(rules=None)
    max_len = P + G + 8

    params = tree_init(h.param_specs(), cell.key, dtype=jnp.bfloat16)
    cache = tree_init(h.serve_state_specs(ShapeCell("serve", "decode", max_len, B)),
                      cell.key)
    tok_spec = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
    prompt_spec = jax.ShapeDtypeStruct((B, P), jnp.int32)
    prefill = jax.jit(program.named(h.prefill(rt), "prefill"), donate_argnums=(1,)).lower(
        params, cache, prompt_spec).compile()
    decode = jax.jit(program.named(h.decode(rt), "decode"), donate_argnums=(1,)).lower(
        params, cache, tok_spec, pos_spec).compile()
    logits_spec = jax.eval_shape(h.decode(rt), params, cache, tok_spec, pos_spec)[0]
    sample = jax.jit(program.named(
        lambda lg: jnp.argmax(lg[:, -1, :V].astype(jnp.float32), axis=-1)[:, None]
        .astype(jnp.int32), "sample")).lower(logits_spec).compile()

    source = traffic_gen.SyntheticSource(B, P - 1, V, cell.seed32)
    state = {"params": params, "cache": cache}
    del params, cache

    ahead = tr["ahead_steps"]

    def serve(gen: int, more) -> tuple[list, list, list]:
        """Serve batches 0, 1, ... of ``gen`` tokens each, starting the next
        while ``more(batches started)`` holds; returns the finished batches
        [(index, tokens (B, gen))], the times to first token and the gaps
        between tokens."""
        inflight = collections.deque()   # (batch, step, token on the device)
        sent: dict = {}                  # batch -> when its prompts were sent
        cur = {"batch": -1, "step": gen, "tok": None}

        def send():
            if cur["step"] == gen:       # the next batch starts
                index = cur["batch"] + 1
                with cell.span("data"):
                    prompts = jnp.asarray(source.batch_at(index))
                sent[index] = time.perf_counter()
                with cell.span("dispatch"):
                    logits, state["cache"] = prefill(state["params"], state["cache"], prompts)
                cur.update(batch=index, step=0)
            else:                        # step s feeds token s - 1 at position P + s - 1
                with cell.span("dispatch"):
                    logits, state["cache"] = decode(state["params"], state["cache"],
                                                    cur["tok"], np.int32(P + cur["step"] - 1))
            with cell.span("sample"):
                cur["tok"] = sample(logits)
                cur["tok"].copy_to_host_async()
            inflight.append((cur["batch"], cur["step"], cur["tok"]))
            cur["step"] += 1

        finished, ttfts, gaps = [], [], []
        rows: dict = {}
        last: dict = {}
        done_at = None                   # when the batch before delivered its last token
        while True:
            while len(inflight) <= ahead and (cur["step"] < gen or more(cur["batch"] + 1)):
                send()
            if not inflight:
                return finished, ttfts, gaps
            index, step, tok = inflight.popleft()
            with cell.span("sync"):
                rows.setdefault(index, []).append(np.asarray(tok))
            t = time.perf_counter()
            if step == 0:
                start = sent[index] if done_at is None else max(sent[index], done_at)
                ttfts.extend([t - start] * B)
            else:
                gaps.append(t - last[index])
            last[index] = t
            if step == gen - 1:
                finished.append((index, np.concatenate(rows.pop(index), axis=1)))
                done_at = t

    serve(3, lambda started: started < 1)  # warm-up: every program and transfer of the window
    n_sample = cell.checks["sample_requests"]
    min_batches = -(-n_sample // B)  # enough finished requests to sample
    with cell.window() as clock:
        finished, ttfts, gaps = serve(
            G, lambda started: clock.elapsed() < cell.seconds or started < min_batches)
    window_s = clock.seconds
    memory = cell.memory_peak_bytes()
    state.clear()
    del prefill, decode, sample
    gc.collect()

    requests = len(finished) * B
    bad = [(i, r) for i, t in finished for r in range(B)
           if not np.all((t[r] >= 0) & (t[r] < V))]

    # the reference over a sample of the finished requests
    rng = np.random.default_rng((cell.seed32, 1))
    picked = sorted(rng.choice(requests, size=n_sample, replace=False).tolist())
    rows, served = [], []
    for j in picked:
        i, r = divmod(j, B)
        tokens = np.clip(finished[i][1][r], 0, V - 1)
        rows.append(np.concatenate([source.batch_at(i)[r], tokens[:-1]]))
        served.append(tokens)
    rows, served = np.stack(rows), np.stack(served)
    weights = dense_lm.make_weights(model, cell.key)
    block = cell.checks.get("block_rows", 1)
    ref = dense_lm.served_logits(model, weights, rows, G, block_rows=block)
    numbers = {"token_gap": float(compare.token_gaps(ref, served).max())}
    variants = {}
    if "fp8" in cell.variants:
        ctrl = dense_lm.served_logits(model, weights, rows, G, dense_lm.FP8, block)
        variants["fp8"] = {"token_gap": float(
            compare.token_gaps(ref, ctrl.argmax(axis=-1)).max())}

    tpot = np.repeat(np.asarray(gaps), B)  # each gap is every sequence's in its batch
    return compare.Outcome(
        metrics={
            "output_tokens_per_s": requests * G / window_s,
            "tpot_p95_ms": float(np.percentile(tpot, 95)) * 1e3,
            "ttft_p90_s": float(np.percentile(ttfts, 90)),
        },
        attempted=requests, failed=len(bad), memory_peak_bytes=memory,
        numbers=numbers, variants=variants,
        counts={"batches": len(finished), "window_s": window_s, "batch": B,
                "ahead_steps": ahead,
                "prompt_len": P, "gen_len": G, "tpot_samples": len(tpot),
                "ttft_samples": len(ttfts), "checked_tokens": int(served.size)},
    )
