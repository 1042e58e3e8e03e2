"""Peak device memory of the serving process on its fullest chip
(``memory_stats()["peak_bytes_in_use"]``, read in that process)."""


def read(ctx):
    peaks = ctx["memory"].get("peak_bytes_in_use") or []
    if not peaks or not max(peaks):
        return None
    return max(peaks) / 2 ** 30
