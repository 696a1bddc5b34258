"""Share of the traced window in which no operation ran on the device while
the host was inside the harness's ``chipbench.write_many`` span: how long
the refresh's writes hold the chip idle.

The cell that reports it, ``ship_tpch4_rf``, writes TPC-H RF1 at SF 30's
rate: 32,728 rows a round. Without a batched ``QueryEngine.write_many`` the
harness falls back to ``write`` row by row, which takes over eight minutes a
round at SF 30, so a run of that cell cannot end within its time. Loading
this reader therefore refuses such a program at once, before any data is
made."""
import ast
from pathlib import Path

from chipbench import catalog

ENGINE_SOURCE = catalog.REPO_ROOT / "src" / "repro" / "runtime" / "engine.py"


def has_write_many(source: Path = ENGINE_SOURCE) -> bool:
    """Whether ``QueryEngine`` in ``source`` defines ``write_many``; read
    from the source, so nothing of the program is imported."""
    for node in ast.parse(source.read_text()).body:
        if isinstance(node, ast.ClassDef) and node.name == "QueryEngine":
            return any(isinstance(f, ast.FunctionDef)
                       and f.name == "write_many" for f in node.body)
    return False


if not has_write_many():
    raise RuntimeError(
        f"{ENGINE_SOURCE}: QueryEngine has no write_many, so RF1's 32,728 "
        f"rows a round would be inserted row by row, over eight minutes a "
        f"round; this program cannot serve ship_tpch4_rf")


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0 or ctx.trace.busy_s <= 0:
        return None
    idle = sum(s for name, s in ctx.trace.idle_gaps if name == "write_many")
    return 100.0 * idle / ctx.trace.window_s
