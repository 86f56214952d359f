"""Log-analytics app (§4.1): error filtering → aggregation → visualization,
showing S3 file handling and the artifacts left in the object store.

    PYTHONPATH=src python examples/log_analytics.py [--log L1] [--config M+C]
"""
import argparse

from repro.apps import log_analytics as la
from repro.core.config import CONFIGS
from repro.core.runtime import FameRuntime


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--log", default="L1", choices=["L1", "L2", "L3"])
    ap.add_argument("--config", default="M+C", choices=sorted(CONFIGS))
    args = ap.parse_args()

    rt = FameRuntime(config=CONFIGS[args.config])
    for role, o in la.build_oracles().items():
        rt.set_llm(role, o)
    rt.deploy_mcp(la.APP.servers, la.APP.sources)

    meta = la.data.LOGS[args.log]
    print(f"log: {meta['path']} ({meta['kind']}, {meta['kb']}KB), "
          f"errors: {meta['errors']}")
    res = rt.run_session(f"la-{args.log}", la.APP.queries(args.log))
    for qi, (resp, st) in enumerate(zip(res.responses, res.statuses)):
        print(f"\nQ{qi + 1} [{st}]: {resp[:200]}")
    print("\nobject-store artifacts:")
    for bucket in ("fame-timestamps", "fame-plots", "fame-mcp-cache"):
        keys = rt.objects.list(bucket)
        print(f"  s3://{bucket}/: {len(keys)} objects "
              f"{keys[:3] if keys else ''}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
