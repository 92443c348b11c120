"""``nmz-tpu sidecar`` — run the persistent search sidecar.

The orchestrator ⇄ JAX boundary of SURVEY.md §5.8: a long-lived process
holding the compiled search plane (device mesh, jitted GA step,
archives) that per-run policies query over loopback instead of paying
search construction + jit warm-up inside every two-second experiment
process. Point a policy at it with ``sidecar = "127.0.0.1:10990"`` in
``explore_policy_param``.

The sidecar is the ONE process that owns the host's chip(s): it searches
on JAX's default backend over a mesh of every device, and a policy in
sidecar mode never initialises a backend of its own (a chip belongs to
one process at a time). Dry runs off the chip: ``JAX_PLATFORMS=cpu``.
"""

from __future__ import annotations


def register(sub) -> None:
    p = sub.add_parser("sidecar", help="persistent search sidecar")
    p.add_argument("--listen", default="127.0.0.1:10990",
                   help="host:port to serve on (default 127.0.0.1:10990)")
    p.add_argument("--pool-dir", default="",
                   help="global failure-pool directory: enables the "
                        "multi-tenant knowledge service (pool_push/"
                        "pool_pull/surrogate_predict/stats ops, "
                        "doc/knowledge.md); empty = search ops only")
    p.add_argument("--state-dir", default="",
                   help="knowledge-service state directory (scenario "
                        "tables, surrogate examples); default: the "
                        "pool dir")
    p.add_argument("--telemetry-url", default="",
                   help="push this process's metrics to a fleet "
                        "aggregator (doc/observability.md \"Fleet "
                        "telemetry\"): http://host:port (orchestrator "
                        "REST) or uds:///path (campaign collector). "
                        "Defaults to $NMZ_TELEMETRY_URL")
    p.set_defaults(func=run_sidecar)


def run_sidecar(args) -> int:
    from namazu_tpu.utils.log import init_log

    init_log()
    from namazu_tpu.sidecar import serve_sidecar

    host, _, port = args.listen.rpartition(":")
    return serve_sidecar(host or "127.0.0.1", int(port),
                         pool_dir=args.pool_dir, state_dir=args.state_dir,
                         telemetry_url=args.telemetry_url)
