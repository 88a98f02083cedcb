"""``memory_stats()["peak_bytes_in_use"]`` after the window, fullest chip.
PR 21 found it near the live arrays (state + batch), NOT the program's
temporaries: see ``device.program_hbm_gb`` for those."""


def read(run):
    return max(run["worker"]["peak_bytes_in_use"]) / 1e9
