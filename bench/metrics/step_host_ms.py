"""Host milliseconds per engine step in the window: each step's time
outside its waits on the device (the engine's ``host_s`` counter, the
``engine.*`` phases but ``engine.sync``), over the steps.  It overlaps
device work wherever programs are queued (a step's standalone prefill
chunks run while the next step is planned and dispatched), so it moves
``output_tok_s`` only by the part the device idles through; the idle
inside ``engine.sync`` (reading outputs, the promotion argmax) lies
outside it, and only a profiler trace splits the two
(``bench.engine_trace``)."""


def read(rec):
    host, steps = rec.counters.get("host_s"), rec.counters.get("iterations")
    return 1e3 * host / steps if host is not None and steps else None
