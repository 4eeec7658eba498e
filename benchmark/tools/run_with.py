"""The benchmark's command with ``root.*`` overrides set first.

    python benchmark/tools/run_with.py [root.a.b=value ...] -- <run.py's
        arguments>

For what a layer of the program costs when it is on: the same cell with
``root.common.telemetry.enabled=False`` (the ring records nothing, no
annotation is entered, the trainer's step histogram stops) against the cell
as the driver runs it.  The overrides are set before the program builds
anything; the cell's own configuration still wins where both name a key.
A run made this way is not the driver's run: say so beside its numbers.
"""

from __future__ import annotations

import ast
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> int:
    cut = argv.index("--")
    from znicz_tpu.core.config import root

    for item in argv[:cut]:
        key, _, value = item.partition("=")
        root.set_by_path(key.removeprefix("root."), ast.literal_eval(value))
    from benchmark import run

    return run.main(argv[cut + 1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
