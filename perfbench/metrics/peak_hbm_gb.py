"""Peak device memory in use over the run, on the fullest chip the cell
uses, in GB (10^9 bytes): the runtime's ``peak_bytes_in_use``, read
after the window."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
