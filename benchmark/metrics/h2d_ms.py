"""Mean per job of the upload (host clock): the program's steps
``partition.put`` (`jax.device_put` until it returns) and ``spmd_sort.h2d``
(the wait until the uploaded arrays are on the device)."""

STEPS = ("partition.put", "spmd_sort.h2d")


def read(run):
    ms = [run.phase_ms(s) for s in STEPS]
    return None if all(v is None for v in ms) else sum(v or 0.0 for v in ms)
