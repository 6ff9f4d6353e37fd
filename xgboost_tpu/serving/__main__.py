"""Entry point: ``python -m xgboost_tpu.serving --model m.bin --port 8080``.

Flag names map 1:1 onto the classic CLI's ``task=serve`` parameters
(``serve_port=...`` -> ``--port``); both surfaces are generated from
``xgboost_tpu.config.SERVE_PARAMS``.
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional

from xgboost_tpu.config import SERVE_PARAMS


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m xgboost_tpu.serving",
        description="Serve an xgboost_tpu model over HTTP "
                    "(batched, recompile-free; see SERVING.md)")
    p.add_argument("--model", required=True,
                   help="model file to serve (watched for hot-reload)")
    p.add_argument("--quiet", action="store_true",
                   help="suppress startup banner and access logs")
    for name, (default, help_) in SERVE_PARAMS.items():
        flag = "--" + name[len("serve_"):].replace("_", "-")
        p.add_argument(flag, type=type(default), default=default,
                       help=f"{help_} (default {default})")
    return p


def main(argv: Optional[List[str]] = None) -> int:
    args = _build_parser().parse_args(argv)
    if argv is None:
        # process entry point: a restarted replica reloads its bucket
        # executables from the persistent jit cache instead of
        # recompiling (compile_cache.py)
        from xgboost_tpu.compile_cache import configure_compile_cache
        configure_compile_cache()
    from xgboost_tpu.serving import run_server
    run_server(args.model, host=args.host, port=args.port,
               min_bucket=args.min_bucket, max_bucket=args.max_bucket,
               max_batch_rows=args.max_batch_rows,
               max_wait_ms=args.max_wait_ms,
               max_queue_rows=args.queue_rows, poll_sec=args.poll_sec,
               keep_versions=args.keep_versions,
               warmup=bool(args.warmup), drain_sec=args.drain_sec,
               max_body_mb=args.max_body_mb,
               featurestore_mb=args.featurestore_mb,
               quiet=args.quiet, block=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
