"""The trial engine's stage phase during the first search: fingerprinting,
host-to-device upload and the staged forms (bf16 copy, power iteration)."""
LAYER, UNIT, SOURCE, MOVES = "stage cache", "s", "program_counter", "first_search_s"


def read(ctx):
    a, b = ctx["counters"]["start"], ctx["counters"]["window_start"]
    return b["phase_stage_s"] - a["phase_stage_s"]
