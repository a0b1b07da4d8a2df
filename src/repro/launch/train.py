"""Training launcher — legacy entrypoint, now a shim over the unified
spec CLI (``python -m repro.launch train``, see launch/cli.py).

Every historical flag (``--arch --optimizer --estimator --q --lr --eps
--sparsity --forward-backend --peft --task --seq-len
--ckpt-dir --ckpt-every --quorum --loss-shards --seed --steps
--batch-size --out``) is accepted unchanged: they are exactly the
generated alias flags of the spec CLI, so there is no per-command
argparse here anymore and the defaults cannot drift from evaluate's.

Part of the unified launch surface (DESIGN.md §11).
"""
from __future__ import annotations

import sys

from repro.launch import cli


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    return cli.main(["train"] + argv)


if __name__ == "__main__":
    main()
