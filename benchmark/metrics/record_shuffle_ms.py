"""Device time per job, busiest chip, of the record sort program's compute
after the splitters' collective: every op of the program that is not a
collective, from its first collective on.

The first collective is the sample's gather of splitters.  After it run
the bucket bounds, the pack gathers of keys, secondary and payload rows
into the send buffers, the combine (`lax.sort`) and its payload row
gather.  The split is by time, not by stage: the local sort's own payload
row gather does not feed the splitters, and where XLA schedules it after
their collective (as on v5e) it is counted here too.  The collectives
themselves are `exchange_ms`.  A one-chip program has no collective and
reads None.
"""

from benchmark.trace import is_collective

#: The record sort program's executions (`_sample_sort_kv*_shard` under jit).
MODULE = r"^jit__sample_sort_kv"


def read(run):
    s = run.trace
    if s is None:
        return None

    def shuffle(name, kind):
        return not is_collective(name, kind)

    whole = s.op_seconds(shuffle, module=MODULE)
    before = s.op_seconds(shuffle, module=MODULE, before_exchange=True)
    t = max(w - b for w, b in zip(whole, before))
    return t / s.jobs * 1e3 if t > 0 else None
