"""Peak device memory on the fullest chip:
`memory_stats()["peak_bytes_in_use"]` after the window."""
UNIT, LAYER, SOURCE, MOVES = "GB", "device", "program_counter", "out_tokens_per_s"


def read(art):
    v = art.get("memory_peak_bytes")
    return None if v is None else v / 1e9
