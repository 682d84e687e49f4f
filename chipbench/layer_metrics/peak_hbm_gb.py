"""Layer `device`: peak bytes on the fullest chip (peak_bytes_in_use plus
peak_bytes_reserved, where this runtime keeps a program's temporaries), in GB."""


def read(run):
    if run["rehearse"] or not run["memory_peak_bytes"]:
        return None
    return run["memory_peak_bytes"] / 1e9
