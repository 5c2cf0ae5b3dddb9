"""Launcher: ``python -m upow_tpu.node.run [--config cfg.json]``
(reference run_node.py / upow/node/run.py)."""

import argparse
import sys

from ..config import Config
from ..device import runtime as device_runtime
from .app import run


def main() -> None:
    parser = argparse.ArgumentParser("upow_tpu node")
    parser.add_argument("--config", default=None)
    parser.add_argument("--port", type=int, default=None)
    parser.add_argument("--db", default=None)
    args = parser.parse_args()
    overrides = {}
    if args.port is not None:
        overrides["node__port"] = args.port
    if args.db is not None:
        overrides["node__db_path"] = args.db
    cfg = Config.load(args.config, **overrides)
    # config device.device, read once, before the node listens: ``tpu``
    # arms now and refuses to start without the chip, ``cpu`` never
    # initialises a TPU backend, ``auto`` probes lazily and degrades
    try:
        info = device_runtime.start(cfg.device.device)
    except (device_runtime.DeviceUnavailable, ValueError) as e:
        print(f"upow_tpu node: {e}", file=sys.stderr, flush=True)
        raise SystemExit(1)
    if info:
        print(device_runtime.device_line(info), flush=True)
    run(cfg)


if __name__ == "__main__":
    main()
