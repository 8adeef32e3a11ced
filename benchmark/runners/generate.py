"""Generation: back-to-back ``InferenceEngineV2.generate()`` calls on
seeded prompt batches that differ per call and were made before the
window, the way a rollout (experience generation) job drives the engine:
fixed shapes, greedy, no early stop, the next batch when the last is back.

``gen_tok_s`` is all the work over all the time, as ``train_tok_s`` is:
the GENERATED tokens (prompt tokens are not counted) of the calls that
finished inside the window over the seconds from the window's opening to
the last of them, on the host clock, per chip. A call is closed by its
token arrays reaching the host (``generate()`` returns numpy rows). A
call is not started where the window's remaining seconds are under nine
tenths of the window's fastest call so far: it could not finish inside,
and what does not finish inside is not counted either way.

Beside it, judged by nothing, the run's detail carries every call's
seconds, what of them lay outside the program's own ``ragged_step`` and
``decode_window`` spans (the host between launches), each call's longest
span, and the spans' medians (a call is one of the first and, at 256 new
tokens in windows of 8, 32 of the second): a stalled call says where.

``correct`` compares what the timed path produced at the timed sizes
with the plain float32 reference on the benchmark's own weights
(``weights.py``), once the window has closed and the engine is gone:

* ``logit_err``: the logits ``put()`` returns for one whole batch of
  prompts, through the ragged step program the window's calls run (same
  rows, same lengths), against the reference's last row, on
  ``check_rows`` rows drawn from the seed: max |d| over max |reference|.
* ``token_gap``: of ``check_rows`` rows drawn from the seed among the
  calls that finished inside the window, the last of them among these,
  every generated token: how far its reference logit lies below the
  reference's best at that position, over max |logit| there; the widest
  over the rows. The reference runs once over each prompt with its
  served tokens. Tokens are not compared for equality: with seeded
  weights the best two logits are often closer than bf16 rounds.
* every call returns ``rows`` x (``prompt_len`` + ``new_tokens``) tokens.

Each limit is in the cell's file with the readings it was set from.
"""

import gc
import statistics
import time

import numpy as np

from .. import arith, arith_gen, tracing
from ..evidence import Evidence, Result, TraceSlice, program_bytes

# the program's spans a generation step opens (telemetry/trace.py),
# mirrored into the profiler's trace: what the host was doing in a gap
SERVE_SPANS = ("ragged_step", "decode_window", "decode_step")
WARM_CALLS = 1
SLICE_AT_CALL = 1           # the traced slice is the window's second call


class GenEvidence(Evidence):
    """``Evidence`` of a generation run: ``launch_rows`` is the (new
    tokens, context) of every row and launch inside the traced slice
    (``arith_gen.generate_call_rows``), which the roofline reader turns
    into the bytes attention had to move there."""
    launch_rows = ()

    def host_spans(self):
        return [e for e in self.events if e.plane.startswith("/host:")
                and e.name in SERVE_SPANS]


class ServeSlice(TraceSlice):
    def events(self):
        import shutil
        path = tracing.newest_xplane(str(self.dir))
        events = tracing.load_events(path, keep_host_line=SERVE_SPANS)
        shutil.rmtree(self.dir, ignore_errors=True)
        return events


class Recorded:
    """A watched jit that notes the argument shapes of every distinct
    launch in ``seen`` and is otherwise the jit (``.lower`` and all)."""

    def __init__(self, fn, seen):
        self.fn, self.seen = fn, seen

    def __call__(self, *args):
        import jax
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
        self.seen.setdefault((self.fn.program, str(shapes)),
                             (self.fn, shapes))
        return self.fn(*args)

    def __getattr__(self, name):
        return getattr(self.fn, name)


class Programs:
    """The engine's watched jits (``telemetry.watchdog.WatchedFunction``
    attributes, whatever they are called), each ``Recorded`` for the
    warm calls, so that the programs the window runs can be handed to
    the compiler for their ``memory_analysis()``. ``release()`` puts the
    engine's own attributes back before the window opens."""

    def __init__(self, engine):
        from deepspeed_tpu.telemetry.watchdog import WatchedFunction
        self.engine = engine
        self.seen = {}              # (program, shapes) -> (fn, shapes)
        self.wrapped = {name: fn for name, fn in vars(engine).items()
                        if isinstance(fn, WatchedFunction)}
        for name, fn in self.wrapped.items():
            setattr(engine, name, Recorded(fn, self.seen))

    def release(self):
        for name, fn in self.wrapped.items():
            setattr(self.engine, name, fn)

    def largest(self):
        """(name, bytes a chip needs, every program's bytes) of the
        largest program launched while recording, by the compiler's own
        analysis (``evidence.program_bytes``)."""
        sized = sorted((program_bytes(fn.lower(*shapes).compile()),
                        fn.program) for fn, shapes in self.seen.values())
        if not sized:
            raise RuntimeError("no watched program was launched")
        size, name = sized[-1]
        return name, size, sized


def make_batches(traffic, vocab, seed):
    """``distinct_batches`` batches of ``rows`` prompts of exactly
    ``prompt_len`` uniform token ids, and one more for the logit probe;
    the same seed gives the same prompts."""
    rng = np.random.default_rng(seed)
    shape = (traffic["rows"], traffic["prompt_len"])
    batches = [rng.integers(0, vocab, shape, dtype=np.int64)
               for _ in range(traffic["distinct_batches"] + 1)]
    return batches[:-1], batches[-1]


def generate(engine, traffic, batch):
    return engine.generate(
        list(batch), max_new_tokens=traffic["new_tokens"],
        temperature=traffic["temperature"], eos_token_id=None,
        speculative=False)


def well_formed(outs, batch, traffic):
    """Rows of a call that came back whole: the prompt unchanged, then
    exactly ``new_tokens`` more ids."""
    want = traffic["prompt_len"] + traffic["new_tokens"]
    return sum(1 for row, prompt in zip(outs, batch)
               if len(row) == want
               and np.array_equal(row[:len(prompt)], prompt))


def logit_error(program_logits, reference_logits):
    """max |d| over max |reference|, both [rows, vocab]."""
    ref = np.asarray(reference_logits, np.float32)
    d = np.abs(np.asarray(program_logits, np.float32) - ref)
    return float(d.max() / np.abs(ref).max())


def token_gaps(reference_logits, tokens):
    """How far each served token lies below the reference's best:
    ``reference_logits`` [n, vocab] are the reference's logits at the
    positions that predict ``tokens`` [n]; (best - served) / max |logit|
    at every position, [n]."""
    import jax.numpy as jnp
    lg = jnp.asarray(reference_logits, jnp.float32)
    tok = jnp.asarray(np.asarray(tokens), jnp.int32)
    served = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
    gap = (jnp.max(lg, axis=-1) - served) / jnp.max(jnp.abs(lg), axis=-1)
    return np.asarray(gap)


def draw_rows(seed, calls, rows, n):
    """``n`` (call, row) pairs drawn from the seed among ``calls``
    finished calls of ``rows`` rows, one of the last call among them."""
    rng = np.random.default_rng([seed, 1])
    n = min(n, calls * rows)
    flat = rng.choice(calls * rows, size=n, replace=False)
    picks = [(int(i) // rows, int(i) % rows) for i in flat]
    if picks and all(c != calls - 1 for c, _ in picks):
        picks[0] = (calls - 1, picks[0][1])
    return picks


def compare(ctx, probe_batch, probe_logits, finished):
    """The numbers ``correct`` rests on, each beside its limit.
    ``finished`` is [(batch, outs)] of the calls that finished inside
    the window. Makes the weights again from the seed (the benchmark's
    own), runs the configuration's reference a row at a time."""
    reference, weights = ctx.reference, ctx.weights
    tr, limits = ctx.traffic, ctx.cell["limits"]
    n_check, plen = tr["check_rows"], tr["prompt_len"]
    params = weights.make(ctx.fields, ctx.seed)
    rng = np.random.default_rng([ctx.seed, 2])
    rows = sorted(rng.choice(len(probe_batch), replace=False,
                             size=min(n_check, len(probe_batch))).tolist())
    ref_last = np.stack([np.asarray(reference.logits(
        params, ctx.fields, probe_batch[r])[-1]) for r in rows])
    numbers = {"logit_err": logit_error(probe_logits[rows], ref_last)}
    gaps = []
    for call, row in draw_rows(ctx.seed, len(finished), tr["rows"],
                               n_check):
        served = np.asarray(finished[call][1][row])
        # the reference at position p predicts the token at p + 1: rows
        # plen-1 .. of its logits over all but the last served token
        lg = reference.logits(params, ctx.fields, served[:-1])
        gaps.append(token_gaps(lg[plen - 1:], served[plen:]))
    if gaps:
        numbers["token_gap"] = float(np.concatenate(gaps).max())
    return {k: {"value": v, "limit": limits[k]["limit"]}
            for k, v in numbers.items()}


def run(ctx):
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.telemetry import trace

    cell, tr = ctx.cell, ctx.traffic
    # the configuration's own modules, found before set-up is spent
    weights, _ = ctx.weights, ctx.reference
    ctx.part("import_program")
    cfg = ctx.model_config()
    params = weights.make(ctx.fields, ctx.seed, cell["engine"]["dtype"])
    engine = InferenceEngineV2(TransformerLM(cfg), cell["engine"],
                               params=params)
    del params
    ctx.log(f"engine up: {engine.attention_impl}, decode window "
            f"{engine.decode_window}, ragged {engine.ragged_enabled}")
    ctx.part("engine_and_weights")
    batches, probe_batch = make_batches(tr, cfg.vocab_size, ctx.seed)
    rows, new = tr["rows"], tr["new_tokens"]
    ctx.part("batches")

    # warm-up, recorded: the logit probe (one put() of a whole batch: the
    # ragged step program of the window's calls, with its logits kept for
    # the comparison), then one whole call on the window's shapes: the
    # ragged program's second launch, every window's first and 31 more
    programs = Programs(engine)
    uids = list(range(rows))
    probe_logits = np.asarray(engine.put(uids, list(probe_batch)),
                              np.float32)
    for uid in uids:
        engine.flush(uid)
    warm_s = []
    for i in range(WARM_CALLS):
        t = time.perf_counter()
        outs = generate(engine, tr, batches[-1 - i])
        warm_s.append(time.perf_counter() - t)
    programs.release()
    ctx.part("warm_calls")
    largest, peak_bytes, sizes = programs.largest()
    ctx.part("memory_analysis")
    ctx.log(f"warm calls {', '.join(f'{s:.2f}' for s in warm_s)} s; "
            f"largest program {largest}: {peak_bytes / 1e9:.3f} GB a chip "
            f"(compiler; all: {sizes}); set-up by part, s: "
            + ", ".join(f"{k} {v:.2f}" for k, v in ctx.setup_parts.items()))

    slicer = ServeSlice(ctx) if ctx.trace else None
    trace.clear()
    ctx.clock.mark()
    setup_s = ctx.setup_seconds()
    ctx.log("window open")
    t0 = time.perf_counter()
    close = t0 + ctx.seconds
    finished, call_s = [], []
    t_last, fastest, sliced, bad_rows = t0, 0.0, 0, 0
    while True:
        slicing = slicer is not None and len(call_s) == SLICE_AT_CALL \
            and not sliced
        if not slicing and t_last + 0.9 * fastest > close:
            break           # a call started now could not finish inside
        batch = batches[len(call_s) % len(batches)]
        if slicing:
            slicer.start()
        outs = generate(engine, tr, batch)
        now = time.perf_counter()
        if slicing:
            slicer.stop()
            sliced = 1
        if now > close:
            break           # this call finished outside the window
        finished.append((batch, outs))
        bad_rows += rows - well_formed(outs, batch, tr)
        call_s.append(now - t_last)
        fastest = min(call_s)
        t_last = now
    compiles = ctx.clock.since_mark()
    chips = len(ctx.devices)
    calls = len(call_s)
    rate = None
    if calls:
        made = sum(arith_gen.generated_tokens(outs, tr["prompt_len"])
                   for _, outs in finished)
        rate = arith.rate(made, t_last - t0) / chips
    ring = [s for s in trace.export() if s["name"] in SERVE_SPANS]
    spans = {name: [s["duration_s"] for s in ring if s["name"] == name]
             for name in ("ragged_step", "decode_window")}
    ends = np.cumsum(call_s) + t0
    in_spans, slowest = calls_spans(ring, ends - np.asarray(call_s), ends)
    ev = GenEvidence(ctx=ctx, compiles_in_window=compiles,
                     slice_steps=sliced, tokens_per_step=rows * new,
                     step_seconds=call_s, memory_peak_bytes=peak_bytes)
    if sliced:
        ev.events = slicer.events()
        ev.launch_rows = arith_gen.generate_call_rows(
            rows, tr["prompt_len"], new)
    # the engine and its pool go before the reference comes
    del engine, programs, outs
    gc.collect()
    numbers = compare(ctx, probe_batch, probe_logits, finished)
    for name, n in numbers.items():
        ctx.log(f"  compared: {name} {n['value']:.4e} (limit "
                f"{n['limit']:.4e})")
    ctx.log(f"  compared: malformed rows {bad_rows} (limit 0), calls "
            f"finished {calls} (at least 1); not compared: compiles in "
            f"the window {compiles}")
    within = all(n["value"] <= n["limit"] for n in numbers.values())
    return Result(
        attempted=calls * rows, failed=bad_rows,
        correct=bool(within and "token_gap" in numbers and bad_rows == 0
                     and calls > 0),
        correct_detail={"compared": numbers, "calls": calls,
                        "call_s": call_s, "warm_call_s": warm_s,
                        "call_outside_spans_s": [
                            c - w for c, w in zip(call_s, in_spans)],
                        "call_slowest_span_s": slowest,
                        "last_call_end_s": t_last - t0,
                        "ragged_step_s": _median(spans["ragged_step"]),
                        "decode_window_s": _median(spans["decode_window"]),
                        "decode_windows": len(spans["decode_window"]),
                        "largest_program": largest,
                        "setup_parts_s": dict(ctx.setup_parts),
                        "compiles_in_window": compiles},
        end_to_end={k: v for k, v in (("gen_tok_s", rate),
                                      ("setup_s", setup_s))
                    if v is not None},
        evidence=ev)


def calls_spans(ring, starts, ends):
    """For each call [start, end): the seconds inside the program's
    spans that began in it, and its longest span. What a call took
    beyond its spans is the host between launches; a stall shows in one
    or the other."""
    inside, slowest = [], []
    for lo, hi in zip(starts, ends):
        mine = [s["duration_s"] for s in ring if lo <= s["start"] < hi]
        inside.append(float(sum(mine)))
        slowest.append(float(max(mine, default=0.0)))
    return inside, slowest


def _median(values):
    return statistics.median(values) if values else None
