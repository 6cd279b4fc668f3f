"""Peak device memory of the fullest chip, ``peak_bytes_in_use`` read
right after the window, before the check allocates anything."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 2 ** 30
