"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, read
when the window closed and before the reference ran, in GB (1e9)."""


def read(readings, params):
    if readings.cell.rehearsal or readings.memory_peak_bytes <= 0:
        return None
    return readings.memory_peak_bytes / 1e9
