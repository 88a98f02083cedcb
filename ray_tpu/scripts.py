"""Command line interface: ``python -m ray_tpu.scripts <command>``.

Reference: ``python/ray/scripts/scripts.py`` (``ray start`` :529,
``status`` :1955, ``submit``, job CLI in ``dashboard/modules/job/cli.py``).
Condensed to the commands that matter for this runtime's topology:

  agent    join a running cluster as a node (the ``ray start`` analog for
           worker nodes: spawns a node_agent against the head address)
  status   cluster resources + nodes, over a client connection
  submit   submit a job (entrypoint command) to the cluster
  jobs     list jobs;  logs/stop act on one job
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _client(args):
    from ray_tpu._private.client import client_connect

    key = args.authkey or os.environ.get("RAY_TPU_CLIENT_AUTHKEY")
    if not key:
        sys.exit("need --authkey or RAY_TPU_CLIENT_AUTHKEY")
    return client_connect(args.address, bytes.fromhex(key))


def _cmd_agent(args):
    os.environ["RAY_TPU_HEAD_ADDRESS"] = args.address
    key = (args.authkey or os.environ.get("RAY_TPU_CLIENT_AUTHKEY")
           or os.environ.get("RAY_TPU_AUTHKEY"))
    if not key:
        sys.exit("need --authkey or RAY_TPU_CLIENT_AUTHKEY")
    os.environ["RAY_TPU_AUTHKEY"] = key
    resources = {"CPU": float(args.num_cpus)}
    if args.num_tpus:
        resources["TPU"] = float(args.num_tpus)
    if args.resources:
        resources.update(json.loads(args.resources))
    os.environ["RAY_TPU_AGENT_RESOURCES"] = json.dumps(resources)
    if args.shm_dir:
        os.environ["RAY_TPU_AGENT_SHM_DIR"] = args.shm_dir
    from ray_tpu._private.node_agent import main as agent_main

    agent_main()


def _cmd_status(args):
    rt = _client(args)
    info = rt.request(lambda rid: ("cluster_info", rid))
    print(f"session: {info['session_id']}")
    print(f"resources: {info['resources']}")
    print(f"available: {info['available']}")
    print(f"nodes ({len(info['nodes'])}):")
    for n in info["nodes"]:
        state = "ALIVE" if n["alive"] else "DEAD"
        print(f"  {n['node_id'][:12]}  {state:5}  {n['resources']}")
    rt.disconnect()


def _cmd_submit(args):
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient(args.address, _authkey=args.authkey)
    runtime_env = json.loads(args.runtime_env) if args.runtime_env else None
    import shlex

    entry = args.entrypoint
    if entry and entry[0] == "--":  # argparse.REMAINDER keeps the separator
        entry = entry[1:]
    # Re-quote: the manager shlex-splits the entrypoint string, so argv
    # tokens with spaces must survive the round trip.
    job_id = client.submit_job(
        entrypoint=" ".join(shlex.quote(t) for t in entry),
        runtime_env=runtime_env)
    print(f"submitted: {job_id}")
    if args.follow:
        for chunk in client.tail_job_logs(job_id, timeout=args.timeout):
            sys.stdout.write(chunk)
            sys.stdout.flush()
        print(f"status: {client.get_job_status(job_id)}")


def _cmd_jobs(args):
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient(args.address, _authkey=args.authkey)
    for j in client.list_jobs():
        print(f"{j['job_id']}  {j['status']:9}  {j['entrypoint']}")


def _cmd_logs(args):
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient(args.address, _authkey=args.authkey)
    sys.stdout.write(client.get_job_logs(args.job_id))


def _cmd_stop(args):
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient(args.address, _authkey=args.authkey)
    print(client.stop_job(args.job_id))


def _cmd_head(args):
    """Run a head process until SIGTERM (the launcher's `ray start
    --head` analog: fixed port + authkey so agents and clients can
    dial)."""
    import signal as _signal
    import time as _time

    import ray_tpu as ray

    rt = ray.init(num_cpus=float(args.num_cpus),
                  _system_config={"authkey_hex": args.authkey,
                                  "listen_port": int(args.port),
                                  "listen_host": args.host})
    print(f"head up at {rt.tcp_address}", flush=True)
    stop = {"flag": False}
    _signal.signal(_signal.SIGTERM,
                   lambda *_: stop.__setitem__("flag", True))
    try:
        while not stop["flag"]:
            _time.sleep(0.5)
    finally:
        ray.shutdown()


def _cmd_up(args):
    from ray_tpu.autoscaler.launcher import up

    up(args.config)


def _cmd_down(args):
    from ray_tpu.autoscaler.launcher import down

    down(args.config)


def _cmd_exec(args):
    import shlex

    from ray_tpu.autoscaler.launcher import exec_cmd

    entry = args.cmd
    if entry and entry[0] == "--":
        entry = entry[1:]
    # shlex re-quoting: argv tokens with spaces/metachars must survive
    # the shell=True round trip intact.
    sys.exit(exec_cmd(args.config,
                      " ".join(shlex.quote(t) for t in entry)))


def _cmd_attach(args):
    from ray_tpu.autoscaler.launcher import attach

    sys.exit(attach(args.config))


def _cmd_timeline(args):
    """``ray timeline`` analog (reference: scripts.py:1840): dump the
    cluster's task spans as chrome://tracing / Perfetto JSON."""
    rt = _client(args)
    try:
        spans = rt.request(
            lambda rid: ("state_req", rid, "spans", {"limit": 200000}))
        if isinstance(spans, Exception):
            raise spans
        from ray_tpu.util.tracing import chrome_trace

        events = chrome_trace(spans)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(events, f)
        print(f"wrote {len(events)} events to {args.out}")
    finally:
        rt.disconnect()


def _cmd_step_breakdown(args):
    """Device time of a profiler trace by step scope and phase
    (``util.tracing.step_breakdown``); needs no cluster."""
    from ray_tpu.util.tracing import format_breakdown, step_breakdown

    b = step_breakdown(args.xplane, args.step_module)
    if b is None:
        sys.exit(f"no two executions of {args.step_module} in {args.xplane}")
    print(format_breakdown(b))
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(b, f, indent=1)


def _cmd_handler_stats(args):
    rt = _client(args)
    try:
        stats = rt.request(
            lambda rid: ("state_req", rid, "handler_stats", {}))
        if isinstance(stats, Exception):
            raise stats
        for s in stats:
            print(f"{s['handler']:>18}  n={s['count']:<8} "
                  f"mean={s['mean_us']:>8.1f}us  max={s['max_ms']:>7.2f}ms "
                  f" total={s['total_ms']:.1f}ms")
    finally:
        rt.disconnect()


def main(argv=None):
    p = argparse.ArgumentParser(prog="ray_tpu",
                                description=__doc__.splitlines()[0])
    sub = p.add_subparsers(dest="command", required=True)

    def common(sp):
        sp.add_argument("--address", required=True,
                        help="head address, tcp://host:port")
        sp.add_argument("--authkey", default=None,
                        help="cluster authkey hex (or env "
                             "RAY_TPU_CLIENT_AUTHKEY)")

    ag = sub.add_parser("agent", help="join the cluster as a node")
    common(ag)
    ag.add_argument("--num-cpus", type=float, default=1.0)
    ag.add_argument("--num-tpus", type=float, default=0.0)
    ag.add_argument("--resources", default=None, help="extra resources JSON")
    ag.add_argument("--shm-dir", default=None)
    ag.set_defaults(fn=_cmd_agent)

    st = sub.add_parser("status", help="cluster resources + nodes")
    common(st)
    st.set_defaults(fn=_cmd_status)

    sb = sub.add_parser("submit", help="submit a job")
    common(sb)
    sb.add_argument("--runtime-env", default=None, help="JSON runtime env")
    sb.add_argument("--follow", action="store_true")
    sb.add_argument("--timeout", type=float, default=600.0)
    sb.add_argument("entrypoint", nargs=argparse.REMAINDER)
    sb.set_defaults(fn=_cmd_submit)

    jb = sub.add_parser("jobs", help="list jobs")
    common(jb)
    jb.set_defaults(fn=_cmd_jobs)

    lg = sub.add_parser("logs", help="print a job's logs")
    common(lg)
    lg.add_argument("job_id")
    lg.set_defaults(fn=_cmd_logs)

    sp = sub.add_parser("stop", help="stop a running job")
    common(sp)
    sp.add_argument("job_id")
    sp.set_defaults(fn=_cmd_stop)

    hd = sub.add_parser(
        "head", help="run a head process (fixed port + authkey)")
    hd.add_argument("--num-cpus", type=float, default=4.0)
    hd.add_argument("--port", type=int, required=True)
    hd.add_argument("--authkey", required=True)
    hd.add_argument("--host", default="127.0.0.1")
    hd.set_defaults(fn=_cmd_head)

    for cname, fn, extra in (("up", _cmd_up, None),
                             ("down", _cmd_down, None),
                             ("attach", _cmd_attach, None)):
        cp = sub.add_parser(
            cname, help=f"{cname} a cluster from a YAML config "
                        f"(launcher; reference: ray {cname})")
        cp.add_argument("config")
        cp.set_defaults(fn=fn)

    ex = sub.add_parser(
        "exec", help="run a shell command wired to a launched cluster")
    ex.add_argument("config")
    ex.add_argument("cmd", nargs=argparse.REMAINDER)
    ex.set_defaults(fn=_cmd_exec)

    tl = sub.add_parser(
        "timeline", help="dump task timeline as Chrome trace JSON")
    common(tl)
    tl.add_argument("--out", default="ray_tpu_timeline.json")
    tl.set_defaults(fn=_cmd_timeline)

    hs = sub.add_parser(
        "handler-stats", help="head per-message-handler latency stats")
    common(hs)
    hs.set_defaults(fn=_cmd_handler_stats)

    bd = sub.add_parser(
        "step-breakdown",
        help="device time of a profiler trace (.xplane.pb) by step scope "
             "and phase")
    bd.add_argument("xplane")
    bd.add_argument("--step-module", default="jit_step")
    bd.add_argument("--json", default=None, help="also write it as JSON")
    bd.set_defaults(fn=_cmd_step_breakdown)

    args = p.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
