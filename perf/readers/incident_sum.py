"""Sum over the program's incidents of one name (``args.incident`` in the
process tracer's incident store, ``progen_tpu.observe.trace``: kept with
the span ring off) that fell in the last N steps of the process: the sum
of ``args.field`` of each, or their count where no field is named, times
``args.scale``.  ``args.where`` (field -> allowed values) narrows them.

N is the number of steps the runner drove in its window — it hands the
readers one entry a step, ``obs["counters"]["queued"]`` — and the last
step's number is the program's counter ``engine.steps``, the process's
count of ``step()`` calls whichever engine made them, which is also the
number an incident carries as the ``step`` it fell in: one numbering, no
clock compared.  The backlog runners step nothing after the window and the
open-loop runner nothing after the drain.  An incident with no step (filed outside any step) is
not the window's.  A program that has no incident store or no step
counter, or a runner that hands over no steps, gives ``None``."""


def read(obs, metric):
    try:
        from progen_tpu.observe.metrics import get_registry
        from progen_tpu.observe.trace import get_tracer
    except ImportError:
        return None
    incidents = getattr(get_tracer(), "incidents", None)
    steps = get_registry().snapshot().get("engine.steps")
    driven = obs.get("counters", {}).get("queued")
    if incidents is None or not steps or driven is None:
        return None
    args = metric["args"]
    first = steps["value"] - len(driven) + 1
    where = args.get("where", {})
    total = 0.0
    for incident in incidents():
        fields = incident.get("args", {})
        if (incident["name"] != args["incident"]
                or fields.get("step") is None or fields["step"] < first
                or any(fields.get(k) not in allowed
                       for k, allowed in where.items())):
            continue
        total += fields[args["field"]] if args.get("field") else 1
    return args.get("scale", 1.0) * total
