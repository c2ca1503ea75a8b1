"""One quantity (``args.quantity``) of the window, computed from the
program's step log: the record every ``ServingEngine.step()`` leaves in the
process tracer's third store (``progen_tpu.observe.trace``:
``Tracer.steps()``, kept with the span ring off).

The window is the last N records of the store, N the number of steps the
runner drove in its window — it hands the readers one entry a step,
``obs["counters"]["queued"]`` — and the last record's ``step`` has to be
the program's counter ``engine.steps``: ``incident_sum.py``'s rule, one
numbering and no clock compared, so the probes, the ramp and whatever else
set-up stepped stay out.  The N records have to be N steps in a row (a step
that raised leaves none), and none may end after the runner's instant for
its step.  That instant counts from the window's opening with the
profiler's start and stop taken off, so the two clocks are laid over each
other at the LAST step, where nothing can be taken off any more; a record
then ends at or before its instant, but for the few microseconds between
the engine's reading of the clock and the runner's (``CLOCK_SLACK_S``).
A store shorter than N, a last step that is not ``engine.steps``, a record
after its instant, a program that has no store (the parent of the PR that
brought it) or a runner that hands over no steps gives ``None``.

The span is the first record's ``t0`` to the last one's ``t0 + wall``, less
the seconds the runner took off its own clock between those steps: the
readers run in the traced run alone, where the profiler is started and
stopped between two steps of the window, and those seconds are neither the
engine's nor in the untraced run that ``serve_tok_s`` comes from.  They
show as the records' clock running ahead of the runner's from one step's
end to the next (``taken_off``).

``admit_share`` / ``chunk_share``: the seconds of the window's stages of
that kind (a record's ``stages``, each closed at the flags fetch that showed
its program done) over the span, in %.  What is in no stage — harvests,
callbacks, host work outside a stage, the caller between steps — is 100
less the two, and no quantity of its own.
``chunk_step_ms``: the seconds of the window's chunk stages over its
chunks times the cell's ``chunk_size``: ``engine.chunk_step_ms.*`` without
the probes and the ramp.
``delivery_gap_ms``: a row's wait between two chunks' tokens.  For each
record that ran a chunk and whose predecessor in the window ran one too,
``t_done`` less the predecessor's ``t_done`` (the return of a step's last
flags fetch) and less what the runner took off between the two, counted
once for each row CARRIED into the chunk (``chunk_rows`` less
``admitted``); the ``args.percentile`` of those by ``perf/lib/stats.py``'s
rule."""

from perf.lib import stats

# seconds by which a record may seem to end after its runner's instant: the
# engine reads the clock before ``step()`` returns and the runner after, a
# few microseconds apart unless the collector runs between them, and a
# pause of 10 ms is an incident of its own (``host.gc``); a step of another
# loop that slipped into the window is a chunk of 150 ms or more
CLOCK_SLACK_S = 0.050


def window(obs):
    """``(records, taken_off)`` of the steps the runner drove, oldest
    first, or None.  ``taken_off[i]`` is the seconds the runner took off
    its clock between the ends of steps ``i - 1`` and ``i``."""
    try:
        from progen_tpu.observe.metrics import get_registry
        from progen_tpu.observe.trace import get_tracer
    except ImportError:
        return None
    steps = getattr(get_tracer(), "steps", None)
    last = get_registry().snapshot().get("engine.steps")
    driven = obs.get("counters", {}).get("queued")
    if steps is None or not last or not driven:
        return None
    records = steps()[-len(driven):]
    if (len(records) < len(driven) or records[-1]["step"] != last["value"]
            or records[-1]["step"] - records[0]["step"] != len(records) - 1):
        return None
    ends = [rec["t0"] + rec["wall"] for rec in records]
    instants = [instant for instant, _ in driven]
    anchor = ends[-1] - instants[-1]
    if any(end > anchor + instant + CLOCK_SLACK_S
           for end, instant in zip(ends, instants)):
        return None
    ahead = [end - instant for end, instant in zip(ends, instants)]
    taken_off = [0.0] + [max(0.0, b - a) for a, b in zip(ahead, ahead[1:])]
    return records, taken_off


def kind(program: str) -> str:
    """``"chunk"``, or the first word of a group as a record prints it:
    ``"('admit', 512, 512)"`` is an ``admit`` stage."""
    return program.split("'")[1] if "'" in program else program


def stage_seconds(records, of=None) -> list:
    """Seconds of each stage of the records, of one kind or of all."""
    return [dt for rec in records for program, dt in rec["stages"]
            if of is None or kind(program) == of]


def delivery_gaps(records, taken_off) -> list:
    """Seconds between two chunks' tokens, once a carried row."""
    gaps = []
    for before, rec, off in zip(records, records[1:], taken_off[1:]):
        if before["chunk_rows"] and rec["chunk_rows"]:
            carried = rec["chunk_rows"] - rec["admitted"]
            gaps += [rec["t_done"] - before["t_done"] - off] * max(carried, 0)
    return gaps


def read(obs, metric):
    found = window(obs)
    if found is None:
        return None
    records, taken_off = found
    args = metric["args"]
    quantity = args["quantity"]
    if quantity in ("admit_share", "chunk_share"):
        of = quantity.removesuffix("_share")
        span = (records[-1]["t0"] + records[-1]["wall"] - records[0]["t0"]
                - sum(taken_off))
        return 100.0 * sum(stage_seconds(records, of)) / span
    if quantity == "chunk_step_ms":
        chunks = stage_seconds(records, "chunk")
        if not chunks:
            return None
        per = obs["workload"]["engine"]["chunk_size"]
        return 1e3 * sum(chunks) / (len(chunks) * per)
    if quantity == "delivery_gap_ms":
        gaps = delivery_gaps(records, taken_off)
        if not gaps:
            return None
        return 1e3 * stats.percentile(gaps, args["percentile"])
    raise ValueError(f"step_log.py has no quantity {quantity!r}")
