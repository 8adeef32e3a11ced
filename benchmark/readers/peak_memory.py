"""What the cell's largest program needs on one chip, in GB (1e9 bytes),
by the compiler's ``memory_analysis()`` (``evidence.program_bytes``)."""


def read(ev, params):
    peak = ev.memory_peak_bytes
    return None if peak is None else peak / 1e9
