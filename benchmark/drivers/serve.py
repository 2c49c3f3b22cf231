"""How a serve window is driven: one ``ServeEngine``, one thread.

The driver makes the weights from the seed, builds the engine with what sizing
needs and nothing else (model, parameters, ``num_blocks``, ``max_slots``),
compiles the shapes this mix can reach by sending requests through
``submit()``/``step()``, runs the mix's lead-in, and opens the window without a
break. Load generator and engine share the thread: requests that are due are
submitted between two engine steps, and each is timed from when it was due.

From the program it reads: ``submit``/``step``/``idle``/``cancel``/``status``/
``output``, ``ledger.records`` (arrival, admitted, first_token, finished,
tokens), ``scheduler.prefilling`` (how far a prompt has got), the bucket lists
and ``compiled_signatures()``, and with ``--trace 1`` the span journal.
"""

from __future__ import annotations

import gc
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference, traffic, weights


def memory_journal(directory):
    """The program's ``SpanJournal`` with every span kept in memory on the
    ``perf_counter`` clock, and none written."""
    from dmlcloud_tpu.telemetry.journal import SpanJournal

    class MemoryJournal(SpanJournal):
        def __init__(self, d):
            super().__init__(d)
            self.spans = []

        def emit(self, kind, start, end=None, label=None, **attrs):
            if end is None:
                end = time.perf_counter()
            self.spans.append({"kind": kind, "start": start, "end": end, "label": label, **attrs})
            self.last_emit = end

    return MemoryJournal(directory)


def build_model(ctx):
    from dmlcloud_tpu.models.hf import transformer_config_from_hf
    from dmlcloud_tpu.models.transformer import DecoderLM

    role = ctx.config["serve"]
    cfg = transformer_config_from_hf(
        types.SimpleNamespace(**ctx.hf, num_hidden_layers=role["num_hidden_layers"]), num_layers=role["num_hidden_layers"],
        max_seq_len=role["max_seq_len"], attn_impl="dot", dtype=jnp.bfloat16,
    )
    return DecoderLM(cfg)


def build_engine(ctx, model):
    from dmlcloud_tpu.serve import ServeEngine

    role = ctx.config["serve"]
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    params = weights.tree_like(ctx.seed, shapes, jnp.bfloat16)
    jax.block_until_ready(params)
    ctx.note(f"weights made at {time.perf_counter() - ctx.t_process:.1f}s")
    return ServeEngine(model, params, num_blocks=role["num_blocks"], max_slots=role["max_slots"])


def bucket_of(size, buckets):
    return next(b for b in buckets if b >= size)


def warm_up(engine, mix) -> int:
    """Compile, through ``submit``/``step``, every (batch x table) shape the
    mix can reach: for each table bucket a decode may need, one request long
    enough to need it runs while ``max_slots - 1`` one-token prompts join it one
    a step, so the decode batch passes through every batch bucket; the long
    prompt's own prefill passes through every table bucket below. Returns the
    engine steps it took."""
    bs, slots = engine.pool.block_size, engine.scheduler.max_slots
    blocks = lambda tokens: -(-tokens // bs)
    lo = bucket_of(blocks(mix["prompt_len"]["min"] + 1), engine.table_buckets)
    hi = bucket_of(blocks(mix["prompt_len"]["max"] + mix["answer_len"]["max"]), engine.table_buckets)
    steps = 0
    for nb in (b for b in engine.table_buckets if lo <= b <= hi):
        below = max((b for b in engine.table_buckets if b < nb), default=0)
        engine.submit(np.zeros(below * bs + 1, np.int32), max_new_tokens=min(2 * slots + 4, nb * bs - below * bs - 1))
        for _ in range(slots - 1):
            engine.submit(np.zeros(1, np.int32), max_new_tokens=slots + 2)
        while not engine.idle:
            engine.step()
            steps += 1
    return steps


def progress(engine, prompt_len_of) -> tuple:
    """(prompt tokens whose chunk has completed, tokens emitted) so far, read
    between two engine steps."""
    records = engine.ledger.records
    prefilled = sum(prompt_len_of[rid] for rid, rec in records.items() if "first_token" in rec and rid in prompt_len_of)
    prefilled += sum(seq.fill for seq in engine.scheduler.prefilling if seq.req.id in prompt_len_of)
    emitted = sum(rec["tokens"] for rid, rec in records.items() if rid in prompt_len_of)
    return prefilled, emitted


def setup(ctx):
    """Weights, engine and every shape of the mix compiled: (model, engine)."""
    model = build_model(ctx)
    engine = build_engine(ctx, model)
    ctx.note(f"weights and engine ready at {time.perf_counter() - ctx.t_process:.1f}s")
    warm_steps = warm_up(engine, ctx.mix)
    ctx.note(f"warm-up: {warm_steps} engine steps, {engine.compiled_signatures()} signatures, "
             f"at {time.perf_counter() - ctx.t_process:.1f}s")
    return model, engine


def run(ctx) -> dict:
    model, engine = setup(ctx)
    result, sample = window(ctx, model, engine, ctx.mix)
    # the program's state leaves the device before the reference comes onto it
    del engine, model
    gc.collect()
    result["checks"] = check(ctx, sample)
    return result


def window(ctx, model, engine, mix) -> tuple:
    """Lead-in, window and drain of ``mix`` on a warm engine: the run's records
    and the sample of served requests for the reference."""
    seconds = ctx.seconds
    annotate = jax.profiler.TraceAnnotation
    vocab = model.cfg.vocab_size
    signatures_warm = engine.compiled_signatures()
    # a traced run records its profile just past the close, with the schedule going on, so that
    # starting and stopping the profiler delays no request that is measured
    trace_len = min(ctx.trace_seconds, 0.5 * seconds) if ctx.trace else 0.0
    requests = traffic.generate(mix, seconds, ctx.seed, vocab, lead_out_s=trace_len)
    backlog = mix["arrivals"]["kind"] == "backlog"
    lead_s = float(mix.get("lead_in_s", 0.0))
    journal = None
    if ctx.trace:
        from dmlcloud_tpu.telemetry import journal as journal_mod

        journal = journal_mod.activate(memory_journal(ctx.tmp_dir("journal")))

    rid_of, submit_at, prompt_len_of = {}, {}, {}
    steps = []  # (start, end) of every engine.step() from the lead-in on
    clock = time.perf_counter
    t_open = clock() + lead_s
    t_close = t_open + seconds
    t_end = t_close + trace_len
    due_at = lambda r: t_open + (r.due_s if r.due_s >= 0 or not backlog else -lead_s)
    order = sorted(range(len(requests)), key=lambda i: (due_at(requests[i]), i))
    nxt = 0
    opened = closed = ended = None
    trace_span = traced = None
    drain_limit = float(mix.get("drain_limit_s", 60.0))

    def submit_due(now):
        nonlocal nxt
        while nxt < len(order) and due_at(requests[order[nxt]]) <= now:
            r = requests[order[nxt]]
            rid = engine.submit(r.prompt, max_new_tokens=r.answer_len)
            rid_of[r.index], submit_at[r.index], prompt_len_of[rid] = rid, clock(), len(r.prompt)
            nxt += 1

    while True:
        now = clock()
        with annotate("bench:loadgen"):
            submit_due(now)
        if opened is None and now >= t_open:
            opened = (now, *progress(engine, prompt_len_of))
            ctx.window_opened(now)
        if closed is None and now >= t_close:
            closed = (now, *progress(engine, prompt_len_of))
            ctx.window_closed()
            if ctx.trace:
                ctx.start_trace()
                traced = annotate("bench:traced")
                trace_span = [clock(), None]
                traced.__enter__()
        if closed is not None and ended is None and now >= t_end:
            ended = now
            if ctx.trace:
                trace_span[1] = clock()
                traced.__exit__(None, None, None)
                ctx.stop_trace()
            if backlog:
                # what the window did not finish was offered to keep the engine
                # fed: it leaves, and nothing is left to drain
                for rid in rid_of.values():
                    engine.cancel(rid)
        if ended is not None and (engine.idle or now >= t_end + drain_limit):
            break
        if engine.idle:
            with annotate("bench:no_request"):
                time.sleep(min(max(due_at(requests[order[nxt]]) - clock(), 0.0), 0.002) if nxt < len(order) else 0.0005)
            continue
        t0 = clock()
        with annotate("bench:engine_step_bookkeeping"):
            engine.step()
        steps.append((t0, clock()))
    if journal is not None:
        from dmlcloud_tpu.telemetry import journal as journal_mod

        journal_mod.deactivate()

    records = engine.ledger.records
    rows = []
    for r in requests:
        rid = rid_of.get(r.index)
        rec = records.get(rid, {}) if rid is not None else {}
        status = engine.status(rid) if rid is not None else "never_submitted"
        rows.append({
            "index": r.index, "rid": rid, "measured": r.measured, "arrival": rec.get("arrival"), "due": due_at(r), "submitted": submit_at.get(r.index),
            "prompt_len": len(r.prompt), "answer_len": r.answer_len, "status": status,
            "admitted": rec.get("admitted"), "first_token": rec.get("first_token"),
            "finished": rec.get("finished"), "tokens": rec.get("tokens", 0),
        })
    finished = [row for row in rows if row["measured"] and row["status"] != "cancelled"] if backlog else \
        [row for row in rows if row["measured"]]
    ok = lambda row: row["status"] == "ok" and row["tokens"] == row["answer_len"]
    sample = pick_sample(ctx, mix, requests, rows, rid_of, engine, ok)
    for row in finished:
        if not ok(row):
            ctx.note(f"request {row['index']} failed: {row['status']}, {row['tokens']} of {row['answer_len']} tokens")
    result = {
        "kind": "serve",
        "window": (opened[0], closed[0]),
        "prompt_tokens_in_window": closed[1] - opened[1],
        "emitted_tokens_in_window": closed[2] - opened[2],
        "requests": rows,
        "attempted": len(finished),
        "failed": sum(not ok(row) for row in finished),
        "steps": steps,
        "spans": journal.spans if journal is not None else None,
        "host_spans": [(s["start"], s["end"], f"bench:inside_{s['kind']}_call") for s in journal.spans
                       if s["kind"] in ("prefill", "decode_batch")] if journal is not None else [],
        "trace_span": trace_span,
        "signatures": (signatures_warm, engine.compiled_signatures()),
        "engine_shapes": {"block_size": engine.pool.block_size, "prefill_chunk": engine.scheduler.prefill_chunk,
                          "num_layers": model.cfg.num_layers},
        "memory_peak_bytes": ctx.memory_peak_bytes(),
    }
    return result, sample


def pick_sample(ctx, mix, requests, rows, rid_of, engine, ok) -> list:
    """The requests the reference will follow: the longest finished one and
    others drawn from the seed, each with the tokens it was served."""
    done = [row["index"] for row in rows if row["measured"] and ok(row)]
    if not done:
        return []
    want = int(mix.get("check_requests", 8))
    longest = max(done, key=lambda i: rows[i]["prompt_len"] + rows[i]["answer_len"])
    rng = np.random.default_rng([int(ctx.seed), 0xC4EC])
    rest = [i for i in rng.permutation(done) if i != longest][: want - 1]
    return [(requests[i].prompt, np.asarray(engine.output(rid_of[i]), np.int32)) for i in [longest, *rest]]


def check(ctx, sample, precision="reference") -> dict:
    """Each number compared, with its limit. ``logit_gap_max``: how far below
    the reference's best logit a served token's logit lies, at worst, over every
    served token of the sample. ``gap_over_0.01_share``: the share of those
    tokens that lie more than 0.01 below it (the steadier of the two)."""
    limits = ctx.config["limits"]["serve"]
    if not sample:
        return {"sampled_requests": {"value": 0, "limit": 1, "ok": False}}
    role = ctx.config["serve"]
    t0 = time.perf_counter()
    gaps = reference.served_token_gaps(ctx.hf, role["num_hidden_layers"], ctx.seed, sample, precision=precision)
    ctx.note(f"reference over {len(sample)} requests took {time.perf_counter() - t0:.1f}s")
    every = np.concatenate(gaps)
    numbers = {"logit_gap_max": float(every.max()), "gap_over_0.01_share": float((every > 0.01).mean())}
    checks = {name: {"value": v, "limit": limits[name], "ok": bool(v <= limits[name])} for name, v in numbers.items()}
    checks["sampled_tokens"] = {"value": int(every.size), "limit": 1, "ok": True}
    return checks
