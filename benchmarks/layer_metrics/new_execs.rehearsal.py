"""Executables a statement new to its session asks the AOT cache for, mean
over the rehearsal's statements: those it compiled plus those it loaded
from disk. The engine keys an executable by every literal and by shapes
the data decides, so this is the count ROADMAP A4 wants cut."""

LAYER = "compile caches"
UNIT = "execs/stmt"
MOVES = "new_stmt_ms"
SOURCE = "program_counter"


def read(run):
    reh = [s for s in run.get("rehearsal", ()) if "aot_loaded" in s]
    if not reh:
        return None
    return sum(s["aot_loaded"] + s["aot_compiled"] for s in reh) / len(reh)
