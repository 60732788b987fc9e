"""Mean per job of the program's step ``partition.pad`` (host clock): the
host pad of the input into the shard layout, secondary plane included."""


def read(run):
    return run.phase_ms("partition.pad")
