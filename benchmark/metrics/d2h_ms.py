"""Mean per job of the program's step ``assemble.d2h`` (host clock): the
waits for each shard's host copy, summed over the shards."""


def read(run):
    return run.phase_ms("assemble.d2h")
