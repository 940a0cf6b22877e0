"""Node entry point: `python -m m3_tpu.server.node_main <config.yaml>`.

Equivalent of the reference's service mains
(`src/cmd/services/m3dbnode/main/main.go` — parse config, server.Run,
block on signals; with an `aggregator:` section the process is a
standalone aggregator, `src/cmd/services/m3aggregator/main`).  Writes a
`<root>/node.json` status file (pid + ports) once serving, so harnesses (dtest) can discover the ephemeral
port; exits cleanly on SIGTERM, flushing the commitlog.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
from pathlib import Path


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    if len(argv) != 1:
        print("usage: python -m m3_tpu.server.node_main <config.yaml>",
              file=sys.stderr)
        return 2
    # The node takes the platform JAX finds: one process per chip.  A
    # launcher that starts several nodes on one host gives its children
    # JAX_PLATFORMS=cpu itself (dtest/harness.NodeProcess).
    from m3_tpu.core.config import load_config
    from m3_tpu.instrument import logger
    from m3_tpu.server.assembly import run_aggregator, run_node
    from m3_tpu.x import jaxcache

    jaxcache.configure()
    log = logger("node_main")
    cfg = load_config(argv[0])
    # a node file with an `aggregator:` section is an aggregator process
    asm = run_aggregator(cfg) if cfg.aggregator is not None else run_node(cfg)
    status = {
        "pid": os.getpid(),
        "port": asm.port,
        "ingest_port": asm.aggregator.port if asm.aggregator else None,
        "msg_port": asm.aggregator.msg_port if asm.aggregator else None,
        "carbon_port": asm.carbon_port,
        "rpc_port": asm.rpc_port,
        "admin_port": asm.admin_port,
        "query_port": asm.query_port,
        "root": cfg.db.root,
    }
    status_path = Path(cfg.db.root) / "node.json"
    status_path.write_text(json.dumps(status))
    log.info("node up: %s", status)

    stop = threading.Event()

    def _term(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    stop.wait()
    # SIGTERM is a true drain, not a fast exit: stop the ingest front
    # doors, flush/snapshot everything persistable, wait (bounded) for
    # any LEAVING shards to cut over to their new owners, then close —
    # the RPC listener serves peer streams until the very end.  The
    # M3_DRAIN_TIMEOUT_S env knob bounds the handoff wait (dtest
    # harnesses shrink it; operators may extend it for big handoffs).
    log.info("node draining")
    asm.drain(handoff_timeout_s=float(
        os.environ.get("M3_DRAIN_TIMEOUT_S", "60")))
    log.info("node shut down")
    status_path.unlink(missing_ok=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
