"""Serving replicas: the dp workers behind the cluster router.

Two deployments share one request-hosting core (`_EngineHost`):

  * `LocalReplica` — in-process replica over its own engine (and, on
    hardware, its own device slice / mp mesh). The router's `pump()`
    drives its engine steps, so a single process can dryrun an
    n-replica cluster deterministically (bench CPU mode, unit tests).
  * `ReplicaWorker` + `RemoteReplica` — a worker PROCESS serving the
    TCP control channel (channel.py), launched either by fleetrun
    (one worker per host, the PADDLE_TRAINER_* env the launcher
    already injects names the replica) or directly via
    `RemoteReplica.spawn`. The worker steps its engine in a loop and
    stamps a heartbeat before every sweep.

Hang handling (the PR-2 machinery wired into serving): a watchdog
thread watches the step-loop heartbeat; when the engine has work but
the heartbeat goes stale past `hang_timeout_s`, it writes a
`replica_hang_report` artifact — flight-recorder ring dump (the mp
collective journal on sharded replicas), all thread stacks, scheduler
census — through the fleet log conventions, and flags the replica
HUNG in its status. The router (router.py) sees the flag (or the
stale heartbeat itself, if the control plane died too) and DRAINS the
replica; the worker stays up for post-mortem instead of wedging the
cluster.
"""
import argparse
import collections
import json
import os
import sys
import threading
import time

from ..scheduler import AdmissionRejected, RequestState
from .channel import ControlClient, ControlServer
from .disagg import DisaggregatedEngine, build_engine

_TERMINAL = (RequestState.FINISHED, RequestState.ABORTED)


def _req_snapshot(rid, req):
    """The drain handoff record: everything a peer needs to resume
    this request (PR-9 resurrect semantics). ONE definition — the
    healthy drain path and the wedged-lock fallback both use it, so a
    new sampling option can't silently drop on one of them."""
    return {
        'rid': rid,
        'prompt': list(req.prompt),
        'generated': list(req.generated),
        'max_new_tokens': req.max_new_tokens,
        'eos_token_id': req.eos_token_id,
        'temperature': req.temperature,
        'top_k': req.top_k,
    }


def _live_requests(engine):
    if isinstance(engine, DisaggregatedEngine):
        return engine.live_requests()
    return [r for r in engine.scheduler.slots if r is not None]


def _waiting_requests(engine):
    if isinstance(engine, DisaggregatedEngine):
        return engine.waiting_requests()
    return list(engine.scheduler.waiting)


def _has_work(engine):
    if isinstance(engine, DisaggregatedEngine):
        return engine.has_work
    return engine.scheduler.has_work


def _decode_engine(engine):
    return engine.decode if isinstance(engine, DisaggregatedEngine) \
        else engine


def _prefix_digest(engine, limit=4096):
    if isinstance(engine, DisaggregatedEngine):
        # affinity cares where PREFILL would hit; decode-side pages
        # resurrect on handoff, so both pools count
        d = set(engine.prefill.pool.prefix_chain_hashes(limit))
        d.update(engine.decode.pool.prefix_chain_hashes(limit))
        return list(d)
    return engine.pool.prefix_chain_hashes(limit)


class _EngineHost:
    """Request hosting shared by LocalReplica and ReplicaWorker:
    submit/poll/status/drain/abort over one engine. Engine access is
    serialized by self._lock (the worker's channel threads race its
    step loop; LocalReplica is single-threaded but pays the uncontended
    lock for one code path)."""

    def __init__(self, engine, replica_id, clock=None):
        self.engine = engine
        self.replica_id = str(replica_id)
        self._clock = clock or time.perf_counter
        self._reqs = {}                 # rid str -> engine Request
        # finished requests keep reporting in poll() until evicted by
        # this capped ring — a poll reply lost to a channel timeout
        # (the client reconnects, the reply dies with the socket) must
        # not lose the completion forever
        self._done = collections.OrderedDict()      # rid -> view
        self._lock = threading.RLock()
        self._draining = False
        self._hung = False
        self._hang_reason = None
        self._beat = self._clock()

    # -- request plane -------------------------------------------------------
    def submit(self, prompt, opts, route_meta=None):
        if self._draining:
            raise RuntimeError(
                f"replica {self.replica_id} is draining")
        with self._lock:
            req = self.engine.submit(list(prompt), **dict(opts or {}))
            if route_meta and self.engine.tracer is not None:
                self.engine.tracer.record(req.id, 'route',
                                          **dict(route_meta))
        rid = str(req.id)
        self._reqs[rid] = req
        return rid

    DONE_RING = 512

    def poll(self):
        with self._lock:
            out = {}
            for rid, req in list(self._reqs.items()):
                view = {'generated': list(req.generated),
                        'state': req.state,
                        'done': req.state in _TERMINAL}
                out[rid] = view
                if view['done']:
                    # terminal views are final — park them in the
                    # ring and keep REPORTING them (idempotently)
                    # until evicted, so one lost reply can't lose
                    # the completion
                    del self._reqs[rid]
                    self._done[rid] = view
                    while len(self._done) > self.DONE_RING:
                        self._done.popitem(last=False)
            for rid, view in self._done.items():
                out.setdefault(rid, view)
        return out

    def status(self):
        now = self._clock()
        with self._lock:
            eng = _decode_engine(self.engine)
            live = [r for r in _live_requests(self.engine)
                    if r.state not in _TERMINAL]
            waiting = _waiting_requests(self.engine)
            pending_tokens = sum(
                max(r.max_new_tokens - len(r.generated), 0)
                + max(len(r.prompt) - r.prefilled, 0)
                for r in live + waiting)
            rate = (eng._decode_tokens / eng._decode_time
                    if eng._decode_time else 0.0)
            # serving ledger view (ISSUE 17): goodput counters + the
            # wall decomposition summary ride the heartbeat so the
            # router's cluster_snapshot() can aggregate without extra
            # RPCs. account() is None until the engine iterated.
            led = getattr(eng, 'ledger', None)
            goodput = led.goodput() if led is not None else None
            acct = led.account() if led is not None else None
            # disaggregated replica: the prefill engine priced the
            # prompt positions on ITS ledger — fold them in so the
            # replica reports the whole pipeline's token stream
            pre = getattr(self.engine, 'prefill', None)
            pre_led = getattr(pre, 'ledger', None) if pre is not None \
                else None
            if goodput is not None and pre_led is not None:
                g2 = pre_led.goodput()
                for k in ('emitted_tokens', 'delivered_tokens',
                          'wasted_tokens', 'spec_shed_tokens'):
                    goodput[k] += g2[k]
                for c, v in g2['wasted_by_cause'].items():
                    goodput['wasted_by_cause'][c] = \
                        goodput['wasted_by_cause'].get(c, 0) + v
                for tid, row in g2['per_tenant'].items():
                    dst = goodput['per_tenant'].setdefault(
                        tid, {'delivered_tokens': 0,
                              'wasted_tokens': 0})
                    dst['delivered_tokens'] += row['delivered_tokens']
                    dst['wasted_tokens'] += row['wasted_tokens']
                goodput['goodput_fraction'] = (
                    goodput['delivered_tokens']
                    / goodput['emitted_tokens']
                    if goodput['emitted_tokens'] else None)
            return {
                'replica_id': self.replica_id,
                'beat_age_s': now - self._beat,
                'hung': self._hung,
                'hang_reason': self._hang_reason,
                'draining': self._draining,
                'waiting': len(waiting),
                'in_flight': len(live),
                'pending_tokens': pending_tokens,
                'decode_tokens_per_sec': rate,
                'degrade_stage': eng.degrade_stage(),
                # fused decode (ISSUE 19): the router polls at window
                # granularity — a replica mid-window reports the last
                # completed window's counters, so beat_age_s can lag
                # by up to k iterations on a healthy fused engine
                'fused_k': eng._effective_fused_k(),
                'fused_windows_total': eng._fused_windows,
                'fused_iterations_total': eng._fused_iterations,
                'timeline': eng.timeline.summary(),
                'pool': {'pages_in_use': eng.pool.pages_in_use,
                         'num_pages': eng.pool.num_pages},
                'prefix_digest': _prefix_digest(self.engine),
                'goodput': goodput,
                'ledger': acct,
                # per-tenant accounting rides the heartbeat too so
                # Router.cluster_snapshot() can expose the
                # per-replica-bucket N x-quota effect (ISSUE 18)
                'tenancy': eng._tenancy_stats(),
            }

    def metrics(self):
        """Compact per-replica metrics snapshot for cluster federation
        (ISSUE 18): the scalar ptpu_serve_* series this engine WOULD
        publish, straight off engine.stats() via the same declarative
        table publish() uses — NOT read back from the process-global
        registry, which in-process LocalReplicas share and would
        cross-contaminate. The router merges these under a `replica`
        label into its federated registry."""
        from .. import metrics as _serve_metrics
        with self._lock:
            eng = _decode_engine(self.engine)
            stats = eng.stats()
            series = _serve_metrics.scalar_series(stats)
            led = getattr(eng, 'ledger', None)
            if led is not None:
                acct = led.account()
                if acct and acct.get('host_bound_fraction') is not None:
                    series['ptpu_serve_ledger_host_bound_fraction'] = \
                        acct['host_bound_fraction']
                good = led.goodput()
                gf = (good or {}).get('goodput_fraction')
                if gf is not None:
                    series['ptpu_serve_goodput_fraction'] = gf
            return {'replica_id': self.replica_id,
                    'beat_age_s': self._clock() - self._beat,
                    'series': series}

    def drain(self):
        """Stop admitting, snapshot + abort every unfinished request.
        The snapshots (prompt, tokens generated so far, remaining
        opts) are what the router resubmits to a peer — the PR-9
        resurrect path, one replica over."""
        self._draining = True
        snaps = []
        with self._lock:
            for rid, req in list(self._reqs.items()):
                if req.state in _TERMINAL:
                    continue
                snaps.append(_req_snapshot(rid, req))
                try:
                    self.engine.abort(req, reason='drained')
                except Exception:           # noqa: BLE001
                    pass
        return snaps

    def prefetch(self, prompt):
        """Advisory host-tier warm (ISSUE 20): the router's
        prefix-affinity hint arrives BEFORE the request and resurrects
        host-resident prefix pages into parked device pages, so the
        submit that follows prefix-hits device pages with the transfer
        off its critical path. Purely advisory — a tierless engine (or
        one whose pages were never spilled) warms nothing, and the
        warm itself never evicts or preempts. Serialized with the step
        loop by self._lock like every other pool mutation."""
        pool = getattr(self.engine, 'pool', None)
        if pool is None or getattr(pool, 'host_tier', None) is None:
            return {'warmed_pages': 0}
        with self._lock:
            prompt = [int(t) for t in prompt]
            n = pool.warm_prefix(prompt, limit=len(prompt) - 1)
        return {'warmed_pages': int(n)}

    def abort(self, rid):
        req = self._reqs.get(str(rid))
        if req is None:
            return False
        with self._lock:
            return bool(self.engine.abort(req))

    def export_trace(self, jsonl_path):
        with self._lock:
            return self.engine.export_trace(jsonl_path=jsonl_path)

    def shutdown(self):
        with self._lock:
            return self.engine.shutdown()


class LocalReplica(_EngineHost):
    """In-process replica: the router pumps its engine directly."""

    def __init__(self, engine, replica_id, clock=None):
        super().__init__(engine, replica_id, clock=clock)
        self._inject_hang = False

    def inject_hang(self):
        """Test hook mirroring ReplicaWorker's: pump() stops stamping
        the heartbeat (and stepping), exactly what a wedged device
        dispatch looks like to the router's watchdog + the
        replica_heartbeat_stale alert rule."""
        self._inject_hang = True
        return {'ok': True}

    def pump(self):
        if self._inject_hang:
            return False
        with self._lock:
            self._beat = self._clock()
            if _has_work(self.engine):
                self.engine.step()
                return True
        return False


class ReplicaWorker(_EngineHost):
    """A replica process: control channel + engine step loop +
    hang watchdog. `run()` blocks in the step loop (the worker
    process's main thread); `start()` runs it on a thread for
    in-process tests."""

    def __init__(self, engine, replica_id, port=0,
                 hang_timeout_s=10.0, report_dir=None, clock=None):
        super().__init__(engine, replica_id, clock=clock)
        self.hang_timeout_s = float(hang_timeout_s)
        self.report_dir = report_dir
        self.last_hang_report_path = None
        self._stop = threading.Event()
        self._inject_hang = False
        self.server = ControlServer(self._handle, port=port).start()
        self.port = self.server.port
        self._watchdog = threading.Thread(
            target=self._watch_loop, name='replica-watchdog',
            daemon=True)
        self._watchdog.start()
        self._loop_thread = None

    # -- control channel -----------------------------------------------------
    def _handle(self, msg):
        op = msg.get('op')
        if op == 'submit':
            try:
                return {'rid': self.submit(msg['prompt'],
                                           msg.get('opts') or {},
                                           msg.get('route'))}
            except AdmissionRejected as e:
                # structured refusal, NOT a channel error: the engine
                # turned the request away (deadline-aware admission,
                # ISSUE 15) — the router must re-raise it as a
                # RouterRejected with the hint, not drain a healthy
                # replica
                return {'rejected': {
                    'reason': e.reason,
                    'retry_after_s': e.retry_after_s,
                    'estimated_s': e.estimated_s,
                    'deadline_s': e.deadline_s}}
        if op == 'poll':
            return {'reqs': self.poll()}
        if op == 'status':
            return self.status()
        if op == 'metrics':
            return self.metrics()
        if op == 'drain':
            return {'inflight': self.drain()}
        if op == 'abort':
            return {'ok': self.abort(msg.get('rid'))}
        if op == 'prefetch':
            # advisory host-tier warm (ISSUE 20) — never an error
            return self.prefetch(msg.get('prompt') or [])
        if op == 'export_trace':
            return {'path': self.export_trace(msg['path'])['jsonl']}
        if op == 'inject_hang':
            # test hook: wedge the step loop (NOT the control plane),
            # exactly what a stuck device dispatch looks like
            self._inject_hang = True
            return {'ok': True}
        if op == 'shutdown':
            self._stop.set()
            return {'ok': True}
        raise ValueError(f"unknown control op {op!r}")

    # status()/drain() intentionally run on the CONTROL thread without
    # waiting for the step loop: when the step loop is wedged inside a
    # dispatch, the lock may be held forever — health probes must not
    # join the hang. The base-class lock methods cover the healthy
    # path; the wedged path reads host lists that Python mutates
    # atomically enough for a diagnostic.
    def status(self):
        if self._lock.acquire(timeout=0.5):
            try:
                return _EngineHost.status(self)
            finally:
                self._lock.release()
        return {
            'replica_id': self.replica_id,
            'beat_age_s': self._clock() - self._beat,
            'hung': self._hung,
            'hang_reason': self._hang_reason,
            'draining': self._draining,
            'waiting': len(_waiting_requests(self.engine)),
            'in_flight': len([r for r in _live_requests(self.engine)
                              if r.state not in _TERMINAL]),
            'pending_tokens': 0,
            'decode_tokens_per_sec': 0.0,
            'degrade_stage': 0,
            'fused_k': 1,
            'fused_windows_total': 0,
            'fused_iterations_total': 0,
            'timeline': {},
            'pool': {},
            'prefix_digest': None,      # keep the router's last view
            'tenancy': None,
        }

    def metrics(self):
        # same wedged-lock discipline as status(): a federation poll
        # must not join a hung step loop — stale beat_age_s and an
        # empty series dict ARE the signal (staleness stamps go quiet)
        if self._lock.acquire(timeout=0.5):
            try:
                return _EngineHost.metrics(self)
            finally:
                self._lock.release()
        return {'replica_id': self.replica_id,
                'beat_age_s': self._clock() - self._beat,
                'series': {}}

    def drain(self):
        if self._lock.acquire(timeout=0.5):
            try:
                return _EngineHost.drain(self)
            finally:
                self._lock.release()
        # wedged: report what we know, abort nothing (the engine
        # thread owns the lock) — the router resubmits from snapshots
        self._draining = True
        return [_req_snapshot(rid, req)
                for rid, req in list(self._reqs.items())
                if req.state not in _TERMINAL]

    # -- step loop + watchdog ------------------------------------------------
    def run(self):
        while not self._stop.is_set():
            if self._inject_hang:
                # simulated wedged dispatch: no heartbeat, no lock
                time.sleep(0.05)
                continue
            self._beat = self._clock()
            with self._lock:
                busy = _has_work(self.engine)
                if busy:
                    self.engine.step()
            if not busy:
                time.sleep(0.002)

    def start(self):
        self._loop_thread = threading.Thread(
            target=self.run, name='replica-step-loop', daemon=True)
        self._loop_thread.start()
        return self

    def stop(self):
        self._stop.set()
        self.server.close()

    def _watch_loop(self):
        fired = False
        while not self._stop.is_set():
            time.sleep(min(self.hang_timeout_s / 4, 0.5))
            age = self._clock() - self._beat
            busy = (self._inject_hang
                    or bool(self._reqs))
            if busy and age > self.hang_timeout_s and not fired:
                fired = True
                self._fire_watchdog(
                    f"step loop heartbeat stale for {age:.1f}s "
                    f"(timeout {self.hang_timeout_s}s)")

    def _fire_watchdog(self, reason):
        """Diagnose + dump a wedged step loop (PR-2 conventions):
        flight-recorder ring (the collective journal on mp-sharded
        replicas — which gather never completed), every thread stack
        (where the loop is stuck), scheduler census. The artifact is
        what `health_dump <path>` renders; the status flag is what the
        router drains on."""
        self._hung = True
        self._hang_reason = reason
        doc = {'kind': 'replica_hang_report',
               'replica_id': self.replica_id,
               'reason': reason,
               'hang_timeout_s': self.hang_timeout_s,
               'waiting': len(_waiting_requests(self.engine)),
               'in_flight': len(_live_requests(self.engine)),
               'requests': {rid: {'state': r.state,
                                  'tokens_generated': len(r.generated)}
                            for rid, r in list(self._reqs.items())}}
        try:
            from ...distributed import flight_recorder as _fr
            doc['flight_recorder'] = _fr.recorder().dump()
            doc['stacks'] = _fr._thread_stacks()
        except Exception as e:              # noqa: BLE001
            doc['flight_recorder_error'] = repr(e)[:200]
        d = (self.report_dir
             or os.environ.get('PTPU_SERVE_REPORT_DIR')
             or os.environ.get('FLEET_LOG_DIR'))
        if d:
            try:
                os.makedirs(d, exist_ok=True)
                path = os.path.join(
                    d, f'replica_hang.{self.replica_id}.json')
                with open(path, 'w') as f:
                    json.dump(doc, f, indent=1, default=str)
                self.last_hang_report_path = path
            except OSError:
                pass
        try:
            from ...distributed.fleet.utils.log_util import log_json
            log_json('replica_hang', level='error',
                     msg=f"serving replica {self.replica_id} hung: "
                         f"{reason}",
                     replica=self.replica_id, reason=reason,
                     report_path=self.last_hang_report_path)
        except Exception:                   # noqa: BLE001
            pass

    def pump(self):
        return False        # the worker's own loop does the stepping


class RemoteReplica:
    """Router-side handle for a ReplicaWorker process."""

    def __init__(self, replica_id, host, port, proc=None,
                 timeout=30.0):
        self.replica_id = str(replica_id)
        self.client = ControlClient(host, port, timeout=timeout)
        self.proc = proc

    @classmethod
    def spawn(cls, replica_id, model_config, engine_config=None,
              seed=0, hang_timeout_s=10.0, env=None,
              ready_timeout_s=300.0):
        """Start `python -m paddle_tpu.serving.cluster.replica` and
        connect once it prints REPLICA_READY (model build + compile
        warmup happen before readiness, so the router never sees a
        cold-compile heartbeat stall)."""
        import subprocess
        cmd = [sys.executable, '-u', '-m',
               'paddle_tpu.serving.cluster.replica',
               '--replica-id', str(replica_id), '--port', '0',
               '--seed', str(seed),
               '--hang-timeout', str(hang_timeout_s),
               '--model-config', json.dumps(model_config),
               '--engine-config', json.dumps(engine_config or {})]
        # the worker runs on the platform its environment names — a
        # CPU worker is asked for through `env`, never defaulted to
        full_env = dict(os.environ)
        full_env.update(env or {})
        proc = subprocess.Popen(cmd, env=full_env,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
        # a reader THREAD feeds a queue so the deadline below holds
        # even against a worker that wedges silently mid-warmup —
        # readline() on the main thread would block past any timeout
        # (exactly the hang class this module defends against)
        import queue as _queue
        q = _queue.Queue()

        def _reader():
            for line in proc.stdout:        # drains post-ready too
                q.put(line)
            q.put(None)

        threading.Thread(target=_reader, daemon=True).start()
        deadline = time.time() + ready_timeout_s
        port = None
        lines = []
        while time.time() < deadline:
            try:
                line = q.get(timeout=min(
                    1.0, max(deadline - time.time(), 0.01)))
            except _queue.Empty:
                if proc.poll() is not None:
                    break
                continue
            if line is None:
                break
            lines.append(line.rstrip())
            if line.startswith('REPLICA_READY'):
                port = int(line.split('port=')[1].strip())
                break
        if port is None:
            proc.kill()
            tail = '\n'.join(lines[-20:])
            raise RuntimeError(
                f"replica {replica_id} never became ready:\n{tail}")
        return cls(replica_id, '127.0.0.1', port, proc=proc)

    def submit(self, prompt, opts, route_meta=None):
        reply = self.client.call({'op': 'submit',
                                  'prompt': [int(t) for t in prompt],
                                  'opts': opts,
                                  'route': route_meta})
        rej = reply.get('rejected')
        if rej is not None:
            raise AdmissionRejected(
                rej.get('reason', 'rejected'),
                retry_after_s=rej.get('retry_after_s'),
                estimated_s=rej.get('estimated_s'),
                deadline_s=rej.get('deadline_s'))
        return reply['rid']

    def poll(self):
        return self.client.call({'op': 'poll'}, timeout=30.0)['reqs']

    def status(self):
        return self.client.call({'op': 'status'}, timeout=5.0)

    def metrics(self):
        return self.client.call({'op': 'metrics'}, timeout=5.0)

    def drain(self):
        return self.client.call({'op': 'drain'},
                                timeout=5.0)['inflight']

    def abort(self, rid):
        return self.client.call({'op': 'abort', 'rid': rid})['ok']

    def prefetch(self, prompt):
        return self.client.call({'op': 'prefetch',
                                 'prompt': [int(t) for t in prompt]},
                                timeout=30.0)

    def export_trace(self, jsonl_path):
        return self.client.call({'op': 'export_trace',
                                 'path': jsonl_path}, timeout=30.0)

    def inject_hang(self):
        return self.client.call({'op': 'inject_hang'})

    def pump(self):
        return False        # remote worker steps itself

    def shutdown(self):
        try:
            self.client.call({'op': 'shutdown'}, timeout=5.0)
        except Exception:                   # noqa: BLE001
            pass
        self.client.close()
        if self.proc is not None:
            try:
                self.proc.wait(timeout=10)
            except Exception:               # noqa: BLE001
                self.proc.kill()


# ---------------------------------------------------------------------------
# worker entrypoint: python -m paddle_tpu.serving.cluster.replica
# ---------------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(
        'paddle_tpu serving replica worker')
    ap.add_argument('--replica-id',
                    default=os.environ.get('PADDLE_TRAINER_ID', '0'))
    ap.add_argument('--port', type=int, default=0)
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--hang-timeout', type=float, default=10.0)
    ap.add_argument('--model-config', default='{}',
                    help='GPTConfig kwargs (JSON)')
    ap.add_argument('--engine-config', default='{}',
                    help='ServingConfig kwargs (JSON)')
    ap.add_argument('--mp', type=int, default=1,
                    help='mp degree inside this replica (device-slice '
                         'mesh; the model is built under a matching '
                         'hcg)')
    args = ap.parse_args(argv)

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingConfig

    mesh = None
    if args.mp > 1:
        import paddle_tpu.distributed.fleet as fleet_mod
        from paddle_tpu.distributed import topology_runtime
        from paddle_tpu.distributed.fleet.base.topology import (
            CommunicateTopology, HybridCommunicateGroup)
        topo = CommunicateTopology(
            ["data", "pipe", "sharding", "model"],
            [1, 1, 1, args.mp])
        fleet_mod.fleet._topology = topo
        fleet_mod.fleet._hcg = HybridCommunicateGroup(topo)
        mesh = topology_runtime.build_mesh(['mp'], [args.mp])

    paddle.seed(args.seed)
    model = GPTForCausalLM(GPTConfig(**json.loads(args.model_config)))
    model.eval()
    engine = build_engine(model,
                          ServingConfig(**json.loads(
                              args.engine_config)), mesh=mesh)
    worker = ReplicaWorker(engine, args.replica_id, port=args.port,
                           hang_timeout_s=args.hang_timeout)
    # compile warmup BEFORE readiness: the standard step shapes
    # (prefill chunk + batched decode) must not stall the heartbeat
    # under first live traffic
    engine.generate([[1, 2, 3]], max_new_tokens=2, top_k=0)
    engine.reset_stats()
    print(f'REPLICA_READY port={worker.port}', flush=True)
    try:
        worker.run()
    finally:
        worker.stop()


if __name__ == '__main__':
    main()
