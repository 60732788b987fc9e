"""Mean per job of the program's step ``assemble.land`` (host clock): the
copies of each shard's rows into the output buffer, summed over the shards."""


def read(run):
    return run.phase_ms("assemble.land")
