"""Decode/serving-path benchmark -> DECODE_r05.json (VERDICT r4
missing: the decode surface was unmeasured code).

Measures on the live chip (gated like the other round tools — a
CPU run writes only a labeled side file):

* prefill tok/s, monolithic (one batched forward filling the cache)
  for the GPT-2-shaped bench model;
* prefill tok/s, chunked (bounded-memory llama_prefill_chunked) for a
  windowed Mistral-tiny config, vs its monolithic prefill — the
  O(chunk*window) claim in wall-clock;
* steady-state decode tok/s (KV-cached lax.scan loop) for both.

The reference has no decode surface (training-only framework) — these
numbers are where "beat the reference" is strict superset capability;
cited in models/generate.py.

Run:  python -u tools/decode_bench.py
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def timed(fn, *args, repeats=3, **kw):
    """Best-of wall clock with block_until_ready, after one warmup
    (compile) call."""
    best, _, out = timed_samples(fn, *args, repeats=repeats, **kw)
    return best, out


def timed_samples(fn, *args, repeats=3, **kw):
    """``(best, samples, out)`` — every repeat's wall clock, for the
    latency percentiles (p50/p99 need the distribution, not just the
    floor)."""
    import jax

    out = fn(*args, **kw)
    jax.block_until_ready(out)
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.block_until_ready(out)
        samples.append(time.perf_counter() - t0)
    return min(samples), samples, out


class VirtualClock:
    """Replica-local time for the disaggregation A/B: each fleet
    member's clock advances only by the measured wall cost of ITS OWN
    scheduler steps — the single-machine-honest model of dedicated
    per-role hardware (this container has one core, so concurrent
    subprocess replicas would just re-serialize on the OS scheduler;
    same fake-clock discipline as the PR-10 overlap acceptance)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def run_disagg_ab(
    seed: int = 11,
    streams: int = 4,
    storms: int = 48,
    stream_max_new: int = 96,
    storm_prompt_len: int = 56,
    storm_max_new: int = 4,
    lanes: int = 6,
    max_len: int = 128,
    verify_outputs: bool = True,
) -> dict:
    """Prefill/decode disaggregation interference A/B (the hermetic
    half of the ISSUE-15 acceptance): the SAME long-prompt storm
    beside the SAME streaming decodes runs through

    * a COLOCATED mixed scheduler — prompt chunks and decode share
      one iteration loop, so every storm-laden step charges its
      prefill budget's wall time to the streams' inter-token gap;
    * a DISAGGREGATED pair — a prefill-role scheduler exports KV
      handoffs a decode-role scheduler imports, each on its own
      virtual clock, so decode ticks are charged ONLY their own
      compute (install + ragged step), never a prompt chunk.

    Step costs are REAL measured wall times of the jitted programs;
    only the concurrency is simulated (virtual per-replica clocks).
    Greedy outputs are verified bitwise against ``generate.generate``
    through the handoff. Returns the per-lane TPOT percentiles of
    the streaming requests (from the schedulers' own TPOT samples —
    the same values the dlrover_serve_tpot_seconds histograms and
    TTFT phase decomposition export)."""
    import numpy as np

    import jax.numpy as jnp

    from dlrover_tpu.models import generate
    from dlrover_tpu.obs.timeseries import _percentile
    from dlrover_tpu.serving.replica import build_tiny_model
    from dlrover_tpu.serving.scheduler import (
        ContinuousBatchingScheduler,
        ServeRequest,
    )

    params, cfg = build_tiny_model(seed, block_size=max_len)
    rng = np.random.default_rng(seed)
    stream_prompts = [
        rng.integers(0, cfg.vocab_size, size=6).tolist()
        for _ in range(streams)
    ]
    storm_prompts = [
        rng.integers(0, cfg.vocab_size, size=storm_prompt_len).tolist()
        for _ in range(storms)
    ]
    warm_stream = rng.integers(0, cfg.vocab_size, size=6).tolist()
    warm_storm = rng.integers(
        0, cfg.vocab_size, size=storm_prompt_len
    ).tolist()

    def make(role, clock, n_lanes):
        return ContinuousBatchingScheduler(
            params, cfg, lanes=n_lanes, block_size=8,
            prefill_chunk=16, prefill_budget=64, max_len=max_len,
            role=role, clock=clock,
        )

    def stepped(sched, clock):
        """One scheduler step, charging its wall cost to the
        scheduler's OWN clock (frozen during the step, so all the
        step's events stamp at step start — per-lane TPOT becomes
        the sum of this loop's own step costs per token)."""
        t0 = time.perf_counter()
        out = sched.step()
        clock.advance(time.perf_counter() - t0)
        return out

    def submit(sched, tag, prompt, max_new):
        assert sched.submit(
            ServeRequest(
                request_id=tag, prompt=list(prompt),
                max_new_tokens=max_new,
            )
        )

    # ---- colocated leg ---------------------------------------------------
    vc = VirtualClock()
    mixed = make("mixed", vc, lanes)
    done: dict = {}

    def drain_mixed(until, budget=20000):
        for _ in range(budget):
            for c in stepped(mixed, vc):
                done[c.request_id] = c
            if until():
                return
        raise RuntimeError("colocated leg did not converge")

    submit(mixed, "warm-s", warm_stream, 3)
    submit(mixed, "warm-l", warm_storm, 2)
    drain_mixed(lambda: "warm-s" in done and "warm-l" in done)
    for i, p in enumerate(stream_prompts):
        submit(mixed, f"stream-{i}", p, stream_max_new)
    drain_mixed(
        lambda: all(
            s.phase == "decode" for s in mixed._by_lane.values()
        ) and mixed.active() == streams
    )
    for i, p in enumerate(storm_prompts):
        submit(mixed, f"storm-{i}", p, storm_max_new)
    drain_mixed(lambda: len(done) == 2 + streams + storms)
    coloc_done = dict(done)
    coloc_tpots = sorted(
        coloc_done[f"stream-{i}"].tpot_s for i in range(streams)
    )

    # ---- disaggregated leg ----------------------------------------------
    vpre, vdec = VirtualClock(), VirtualClock()
    pre = make("prefill", vpre, lanes)
    dec = make("decode", vdec, lanes)
    done = {}

    def pump(until, budget=40000):
        """Alternate the two replicas' loops; handoffs flow prefill
        -> decode; each loop's cost lands on its own clock."""
        for _ in range(budget):
            for c in stepped(pre, vpre):
                if c.finish_reason == "handoff":
                    assert dec.submit_handoff(c.handoff)
                else:
                    done[c.request_id] = c
            for c in stepped(dec, vdec):
                done[c.request_id] = c
            if until():
                return
        raise RuntimeError("disaggregated leg did not converge")

    submit(pre, "warm-s", warm_stream, 3)
    submit(pre, "warm-l", warm_storm, 2)
    pump(lambda: "warm-s" in done and "warm-l" in done)
    for i, p in enumerate(stream_prompts):
        submit(pre, f"stream-{i}", p, stream_max_new)
    pump(lambda: dec.active() == streams)
    for i, p in enumerate(storm_prompts):
        submit(pre, f"storm-{i}", p, storm_max_new)
    pump(lambda: len(done) == 2 + streams + storms)
    disagg_done = dict(done)
    disagg_tpots = sorted(
        disagg_done[f"stream-{i}"].tpot_s for i in range(streams)
    )

    # ---- bitwise parity through the handoff ------------------------------
    # Every stream + a storm sample: each reference generate.generate
    # call re-traces (distinct shapes), so verifying all 48 storms
    # would cost more wall time than the A/B itself — the failover
    # drill leg verifies EVERY request end-to-end over RPC.
    mismatched = []
    cases = {}
    if verify_outputs:
        for i, p in enumerate(stream_prompts):
            cases[f"stream-{i}"] = (p, stream_max_new)
        for i, p in enumerate(storm_prompts[:8]):
            cases[f"storm-{i}"] = (p, storm_max_new)
        for rid, (prompt, max_new) in cases.items():
            want = np.asarray(
                generate.generate(
                    params, cfg, jnp.asarray([prompt], jnp.int32),
                    max_new_tokens=max_new, temperature=0.0,
                )
            )[0, len(prompt):].tolist()
            for leg, results in (
                ("colocated", coloc_done),
                ("disagg", disagg_done),
            ):
                if results[rid].tokens != want:
                    mismatched.append((leg, rid))
    if mismatched:
        raise AssertionError(
            "greedy outputs diverged from generate.generate: "
            f"{mismatched[:4]}"
        )

    coloc_p99 = _percentile(coloc_tpots, 99.0)
    disagg_p99 = _percentile(disagg_tpots, 99.0)
    return {
        "seed": seed,
        "streams": streams,
        "storms": storms,
        "stream_max_new": stream_max_new,
        "storm_prompt_len": storm_prompt_len,
        "storm_max_new": storm_max_new,
        "lanes": lanes,
        "prefill_chunk": 16,
        "prefill_budget": 64,
        "coloc_p50_tpot_s": round(_percentile(coloc_tpots, 50.0), 6),
        "coloc_p99_tpot_s": round(coloc_p99, 6),
        "disagg_p50_tpot_s": round(
            _percentile(disagg_tpots, 50.0), 6
        ),
        "disagg_p99_tpot_s": round(disagg_p99, 6),
        "tpot_p99_ratio": round(
            disagg_p99 / max(coloc_p99, 1e-12), 4
        ),
        "handoffs": pre.stats()["handoffs_exported"],
        # Verified in BOTH legs (every stream + a storm sample; the
        # failover drill leg verifies every request over RPC).
        "outputs_verified": 2 * len(cases),
    }


def disagg_mode() -> int:
    """``--disagg``: record the colocated/disaggregated kind-decode
    ledger pair and verify the regression gate reads it
    lower-is-better (the ISSUE-15 bench satellite). DECODE_LEDGER=0
    routes the records to a throwaway ledger so CI never pollutes
    the history (the gate is still exercised end to end)."""
    import tempfile

    from bench_ledger import append_record, compare

    rec = run_disagg_ab()
    ok = rec["disagg_p99_tpot_s"] < rec["coloc_p99_tpot_s"]
    print(
        f"[disagg] stream p99 TPOT: colocated "
        f"{rec['coloc_p99_tpot_s']}s vs disaggregated "
        f"{rec['disagg_p99_tpot_s']}s "
        f"(x{rec['tpot_p99_ratio']}, {rec['handoffs']} handoffs, "
        f"{rec['outputs_verified']} outputs bitwise-verified)",
        flush=True,
    )
    path = None
    if os.environ.get("DECODE_LEDGER", "1") == "0":
        path = tempfile.mktemp(suffix=".jsonl")
    roles = {
        "colocated": {"mixed": 1},
        "disagg": {"prefill": 1, "decode": 1},
    }
    for label, value in (
        ("colocated", rec["coloc_p99_tpot_s"]),
        ("disagg", rec["disagg_p99_tpot_s"]),
    ):
        stored = append_record(
            {
                "kind": "decode",
                "metric": "decode_p99_tpot_seconds",
                "value": value,
                "unit": "s",
                "label": label,
                # The role config is part of the record's pins: a
                # compare across fleet shapes must SAY it compared
                # fleet shapes.
                "pins": {
                    "roles": roles[label],
                    "lanes": rec["lanes"],
                    "prefill_chunk": rec["prefill_chunk"],
                    "prefill_budget": rec["prefill_budget"],
                    "storms": rec["storms"],
                    "storm_prompt_len": rec["storm_prompt_len"],
                },
            },
            path=path,
        )
        print(
            f"[disagg] ledger += decode_p99_tpot_seconds "
            f"{stored.get('value')} s ({label})",
            flush=True,
        )
    code, report = compare(
        baseline="colocated",
        head="disagg",
        metric="decode_p99_tpot_seconds",
        path=path,
    )
    print(report, flush=True)
    if "(lower is better)" not in report:
        print(
            "[disagg] FAIL: compare did not gate "
            "decode_p99_tpot_seconds lower-is-better",
            file=sys.stderr,
        )
        return 1
    if code != 0 or not ok:
        print(
            "[disagg] FAIL: disaggregated p99 TPOT did not beat "
            "colocated",
            file=sys.stderr,
        )
        return 1
    return 0


def main() -> int:
    if "--disagg" in sys.argv[1:]:
        return disagg_mode()
    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from dlrover_tpu.models import generate, llama

    on_tpu = jax.default_backend() == "tpu"
    small = os.environ.get("DECODE_SMALL") == "1" or not on_tpu
    rec: dict = {
        "backend": jax.default_backend(),
        "full_scale": not small,
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }

    def ckpt():
        """Measured-so-far checkpoint: a run that dies mid-section
        must not erase completed sections (the r5 longctx lesson)."""
        json.dump(rec, open("/tmp/decode_partial.json", "w"), indent=1)

    # --- GPT-2-shaped Llama-family config (the bench model's shape) --
    if small:
        cfg = llama.LlamaConfig.tiny()
        cfg = dataclasses.replace(cfg, block_size=128)
        b, t_prompt, new = 2, 64, 32
        mcfg = dataclasses.replace(
            llama.LlamaConfig.tiny(), sliding_window=16, block_size=128
        )
        m_prompt, chunk = 96, 32
    else:
        cfg = llama.LlamaConfig(
            vocab_size=50304, block_size=2048, n_layer=12, n_head=12,
            n_kv_head=12, n_embd=768, intermediate=3072,
            dtype=jnp.bfloat16,
        )
        b, t_prompt, new = 8, 1024, 512
        # Mistral-tiny: the 4096-token band binding inside an 8k
        # prompt, GQA 4:1 — the sliding-window serving regime.
        mcfg = llama.LlamaConfig(
            vocab_size=32000, block_size=16384, n_layer=8, n_head=16,
            n_kv_head=4, n_embd=1024, intermediate=3584,
            dtype=jnp.bfloat16, sliding_window=4096,
        )
        m_prompt, chunk = 8192, 1024

    key = jax.random.PRNGKey(0)
    params = llama.init_params(key, cfg)
    prompt = jax.random.randint(
        jax.random.fold_in(key, 1), (b, t_prompt), 0, cfg.vocab_size
    )

    # Monolithic prefill tok/s.
    total = t_prompt + new
    cache = generate._cache_for(cfg, b, total, cfg.n_kv_head)
    pre_fn = jax.jit(
        lambda p, c, tok: generate.llama_prefill(p, c, tok, cfg)
    )
    dt, (logits, filled) = timed(pre_fn, params, cache, prompt)
    rec["gpt2_prefill_ms"] = round(dt * 1e3, 2)
    rec["gpt2_prefill_tok_s"] = round(b * t_prompt / dt, 1)
    print(f"[decode] gpt2-shape prefill: {dt*1e3:.1f} ms "
          f"({rec['gpt2_prefill_tok_s']} tok/s)", flush=True)
    ckpt()

    # Steady-state decode tok/s: difference two generate lengths so
    # prefill and fixed overheads cancel exactly (subtracting a
    # separately-jitted prefill underflows when the two programs
    # optimize differently).
    def gen_at(n_new, samples=False):
        fn = jax.jit(
            lambda p, pr, k: generate.generate(
                p, cfg, pr, max_new_tokens=n_new, temperature=0.0,
                key=k,
            )
        )
        d, walls, _ = timed_samples(
            fn, params, prompt, jax.random.PRNGKey(2), repeats=5
        )
        return (d, walls) if samples else d

    half = max(new // 2, 1)
    (dt_full, full_walls), dt_half = (
        gen_at(new, samples=True), gen_at(new - half)
    )
    decode_s = max(dt_full - dt_half, 1e-9)
    rec["gpt2_generate_ms"] = round(dt_full * 1e3, 2)
    rec["gpt2_decode_tok_s"] = round(b * half / decode_s, 1)
    rec["gpt2_decode_ms_per_tok"] = round(decode_s / half * 1e3, 3)
    print(f"[decode] gpt2-shape decode: {rec['gpt2_decode_tok_s']} "
          f"tok/s ({rec['gpt2_decode_ms_per_tok']} ms/tok, "
          f"batch {b})", flush=True)
    ckpt()

    # Latency distributions (the serving SLO pair): TTFT = a 1-token
    # generate (prefill + first token), sampled per repeat; TPOT =
    # per-repeat (full_wall - best_half_wall) / half. p50/p99 use the
    # one shared nearest-rank formula so the bench's gates measure
    # the same quantity as the router's exported gauges.
    from dlrover_tpu.obs.timeseries import _percentile

    _, ttft_walls = gen_at(1, samples=True)
    tpot_samples = sorted(
        max(w - dt_half, 1e-9) / half for w in full_walls
    )
    ttft_samples = sorted(ttft_walls)
    rec["ttft_p50_s"] = round(_percentile(ttft_samples, 50.0), 4)
    rec["ttft_p99_s"] = round(_percentile(ttft_samples, 99.0), 4)
    rec["tpot_p50_s"] = round(_percentile(tpot_samples, 50.0), 5)
    rec["tpot_p99_s"] = round(_percentile(tpot_samples, 99.0), 5)
    print(f"[decode] gpt2-shape latency: ttft p50/p99 "
          f"{rec['ttft_p50_s']}/{rec['ttft_p99_s']}s, tpot p50/p99 "
          f"{rec['tpot_p50_s']}/{rec['tpot_p99_s']}s", flush=True)
    ckpt()

    # --- windowed Mistral-tiny: chunked vs monolithic prefill --------
    mparams = llama.init_params(jax.random.fold_in(key, 3), mcfg)
    mprompt = jax.random.randint(
        jax.random.fold_in(key, 4), (1, m_prompt), 0, mcfg.vocab_size
    )
    mcache = generate._cache_for(mcfg, 1, m_prompt + 8, mcfg.n_kv_head)
    mono_fn = jax.jit(
        lambda p, c, tok: generate.llama_prefill(p, c, tok, mcfg)
    )
    dt_mono, _ = timed(mono_fn, mparams, mcache, mprompt)
    rec["mistral_prefill_mono_ms"] = round(dt_mono * 1e3, 2)

    # jit the whole chunk loop (it unrolls at trace time) so both
    # prefill paths compare as compiled programs — unjitted, the
    # chunked path would pay per-op dispatch the monolithic one
    # doesn't.
    chunked = jax.jit(
        lambda p, c, tok: generate.llama_prefill_chunked(
            p, c, tok, mcfg, chunk_size=chunk
        )
    )

    dt_chunk, _ = timed(chunked, mparams, mcache, mprompt)
    rec["mistral_prefill_chunked_ms"] = round(dt_chunk * 1e3, 2)
    rec["mistral_prompt"] = m_prompt
    rec["mistral_window"] = mcfg.sliding_window
    rec["mistral_chunk"] = chunk
    rec["chunked_over_mono"] = round(dt_chunk / dt_mono, 2)
    print(f"[decode] mistral prefill {m_prompt} tokens: "
          f"mono {dt_mono*1e3:.1f} ms vs chunked {dt_chunk*1e3:.1f} ms",
          flush=True)
    ckpt()

    # Windowed decode tok/s — same two-length differencing.
    m_new = 8 if small else 128

    def mgen_at(n_new):
        fn = jax.jit(
            lambda p, pr, k: generate.generate(
                p, mcfg, pr, max_new_tokens=n_new, temperature=0.0,
                key=k,
            )
        )
        d, _ = timed(fn, mparams, mprompt, jax.random.PRNGKey(5))
        return d

    m_half = max(m_new // 2, 1)
    dt_mfull, dt_mhalf = mgen_at(m_new), mgen_at(m_new - m_half)
    mdecode_s = max(dt_mfull - dt_mhalf, 1e-9)
    rec["mistral_decode_tok_s"] = round(m_half / mdecode_s, 1)
    rec["mistral_decode_ms_per_tok"] = round(
        mdecode_s / m_half * 1e3, 3
    )
    print(f"[decode] mistral decode: {rec['mistral_decode_tok_s']} "
          f"tok/s at context {m_prompt}", flush=True)
    ckpt()

    # Artifact convention (tools/README.md): only full-size hardware
    # runs write the repo-root round record; smoke runs go to /tmp.
    out = (
        os.path.join(REPO, "DECODE_r05.json")
        if (on_tpu and not small)
        else "/tmp/decode_bench_smoke.json"
    )
    json.dump(rec, open(out, "w"), indent=1)
    print(f"[decode] wrote {out}", flush=True)

    # Regression gate: decode throughput rides the same fingerprinted
    # append-only ledger as training (BENCH_LEDGER.jsonl; record kind
    # "decode"), so a serving-path regression trips
    # `bench_ledger compare --metric decode_tokens_per_sec` exactly
    # like a train-step one. DECODE_LEDGER=0 skips (sweeps that
    # should not pollute the history).
    if os.environ.get("DECODE_LEDGER", "1") != "0":
        from bench_ledger import append_record

        for metric, value, unit, extra in (
            (
                "decode_tokens_per_sec",
                rec["gpt2_decode_tok_s"],
                "tok/s",
                {
                    "prefill_tok_s": rec["gpt2_prefill_tok_s"],
                    "ms_per_tok": rec["gpt2_decode_ms_per_tok"],
                    "batch": b,
                },
            ),
            (
                "decode_windowed_tokens_per_sec",
                rec["mistral_decode_tok_s"],
                "tok/s",
                {
                    "context": m_prompt,
                    "window": mcfg.sliding_window,
                    "chunked_over_mono": rec["chunked_over_mono"],
                },
            ),
            # Latency gates: `bench_ledger compare --metric
            # decode_ttft_p99_s` (or decode_tpot_p99_s) trips on a
            # latency regression, not just a throughput one.
            (
                "decode_ttft_p99_s",
                rec["ttft_p99_s"],
                "s",
                {"p50": rec["ttft_p50_s"], "batch": b},
            ),
            (
                "decode_tpot_p99_s",
                rec["tpot_p99_s"],
                "s",
                {"p50": rec["tpot_p50_s"], "batch": b},
            ),
        ):
            stored = append_record(
                {
                    "kind": "decode",
                    "metric": metric,
                    "value": value,
                    "unit": unit,
                    "backend": rec["backend"],
                    "full_scale": rec["full_scale"],
                    **extra,
                },
                backend=rec["backend"],
            )
            print(
                f"[decode] ledger += {metric} "
                f"{stored.get('value')} {unit} "
                f"(rev {str(stored.get('git_rev', ''))[:12]})",
                flush=True,
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())
