"""Served erasure-code operations in a closed loop: client writes
(``op: encode``) or recovery reads of degraded objects (``op:
decode``), through the library's serving front door.

Path: ``AdmissionQueue.submit`` -> ``ContinuousBatcher.poll`` as the
library builds them by default -> ``codes/engine.serve_dispatch_call``
-> the kernel tier the engine picks.  Host arrays go in and come out.

Traffic parameters (``benchmark/traffic/<mix>.json``):

- ``op``: ``encode`` or ``decode``;
- ``object_bytes``: an object is ``object_bytes / stripe_width``
  stripes, one request each; it completes when its last stripe is
  back on the host;
- ``outstanding_objects``: the closed loop's depth (clients x ops in
  flight per client);
- ``dataset_objects``: distinct seeded objects the loop cycles over,
  in a seeded order;
- ``lost_shards`` (decode): shards each stored object has lost, the
  positions spread evenly over the data set and dealt to the objects
  in a seeded order; helpers come from ``minimum_to_decode``;
- ``check_every`` / ``check_ops``: ops are drawn for the comparison
  with the plain reference with probability 1/check_every, at most
  check_ops of them.

The stored chunks a decode reads are the library's own encode of the
seeded data, as an OSD would hold them; the comparison takes its truth
from the seeded data and the plain reference alone.
"""

from __future__ import annotations

import gc
import importlib
import sys
import time

import numpy as np

from ..harness import Check, Window, span, stage

MAX_OPS = 1 << 20
DRAIN_S = 60.0


def least_bytes_per_stripe(op: str, plugin: str, profile: dict, chunk: int,
                           lost: int) -> float:
    """The least HBM bytes one stripe's operation must move.

    Encode reads k chunks and writes m.  A clay single-chunk repair
    reads d * C / (d - k + 1) helper bytes (its repair bandwidth) and
    writes C; any other decode reads k chunks and writes the lost ones.
    """
    k, m = int(profile["k"]), int(profile["m"])
    if op == "encode":
        return float((k + m) * chunk)
    if plugin == "clay" and lost == 1:
        d = int(profile.get("d", k + m - 1))
        return d * chunk / (d - k + 1) + chunk
    return float((k + lost) * chunk)


class Cell:
    def __init__(self, spec, seed: int) -> None:
        self.spec = spec
        self.seed = seed
        cfg, tr = spec.config, spec.traffic
        self.plugin = cfg["plugin"]
        self.profile = {str(k): str(v) for k, v in cfg["profile"].items()}
        self.stripe_width = int(cfg["stripe_width"])
        self.op = tr["op"]
        if self.op not in ("encode", "decode"):
            raise ValueError(f"op {self.op!r} must be encode or decode")
        self.object_bytes = int(tr["object_bytes"])
        self.stripes = self.object_bytes // self.stripe_width
        self.outstanding = int(tr["outstanding_objects"])
        self.dataset = int(tr["dataset_objects"])
        self.lost = int(tr.get("lost_shards", 0))
        rng = np.random.default_rng([seed, 2])
        picks = np.nonzero(rng.random(MAX_OPS) < 1.0 / tr["check_every"])[0]
        self.sampled = set(picks[:int(tr["check_ops"])].tolist())

    # -- set-up --------------------------------------------------------

    def setup(self) -> None:
        from ceph_tpu.codes.registry import ErasureCodePluginRegistry
        from ceph_tpu.serve.batcher import ContinuousBatcher
        from ceph_tpu.serve.queue import AdmissionQueue

        t0 = time.perf_counter()
        ec = ErasureCodePluginRegistry.instance().factory(
            self.plugin, dict(self.profile))
        self.k = ec.get_data_chunk_count()
        self.n = ec.get_chunk_count()
        self.chunk = ec.get_chunk_size(self.stripe_width)
        rng = np.random.default_rng(self.seed)
        shape = (self.dataset, self.stripes, self.k, self.chunk)
        self.data = np.frombuffer(rng.bytes(int(np.prod(shape))),
                                  np.uint8).reshape(shape)
        self.order = rng.permutation(self.dataset)
        stage("data", t0)
        if self.op == "decode":
            self._store(ec, rng)
            stage("stored chunks", t0)
        self.least = least_bytes_per_stripe(
            self.op, self.plugin, self.profile, self.chunk,
            self.lost or 0)
        self.batcher = ContinuousBatcher()
        self.queue = AdmissionQueue()
        seen, warm = set(), []
        for obj in range(self.dataset):
            key = self._pattern(obj)
            if key not in seen:
                seen.add(key)
                warm.append(self._request(obj, 0))
        self.batcher.warmup(warm)
        stage(f"warm-up of {len(warm)} buckets", t0)

    def _store(self, ec, rng) -> None:
        """Encode the data set as the OSDs hold it, lose shards, keep
        each object's helper chunks in read order."""
        d, s, k, c = self.data.shape
        parity = np.asarray(ec.encode_chunks_batch(
            self.data.reshape(d * s, k, c))).reshape(d, s, -1, c)
        full = np.concatenate([self.data, parity], axis=2)
        del parity
        # every shard position loses equally often (as far as d
        # objects allow); the seed decides which object loses which, so
        # every seed brings the same mix of decode patterns
        lost = [tuple(sorted((i * self.n // d + j) % self.n
                             for j in range(self.lost))) for i in range(d)]
        self.lost_ids = [lost[i] for i in rng.permutation(d)]
        self.avail = []
        helpers = None
        for obj, lost in enumerate(self.lost_ids):
            rest = set(range(self.n)) - set(lost)
            avail = tuple(sorted(ec.minimum_to_decode(set(lost), rest)))
            self.avail.append(avail)
            if helpers is None:
                helpers = np.empty((d, s, len(avail), c), np.uint8)
            helpers[obj] = full[obj][:, list(avail)]
        self.helpers = helpers

    def _pattern(self, obj: int):
        if self.op == "encode":
            return ()
        return (self.avail[obj], self.lost_ids[obj])

    def _request(self, obj: int, stripe: int):
        from ceph_tpu.serve.queue import EcRequest
        if self.op == "encode":
            return EcRequest(op="encode", plugin=self.plugin,
                             profile=self.profile,
                             stripe_size=self.stripe_width,
                             payload=self.data[obj, stripe])
        return EcRequest(op="decode", plugin=self.plugin,
                         profile=self.profile,
                         stripe_size=self.stripe_width,
                         payload=self.helpers[obj, stripe],
                         available=self.avail[obj],
                         erased=self.lost_ids[obj])

    # -- the window ----------------------------------------------------

    def window(self, seconds: float) -> Window:
        b, q = self.batcher, self.queue
        clock = b.clock
        free = list(range(self.outstanding))
        # op -> [object, submitted, stripes left, last stripe back, slot]
        self.ops = {}
        self.req_op = {}       # request id -> (op, stripe)
        self.outputs = {}      # sampled op -> {stripe: output}
        self.objs = {}         # sampled op -> object
        lat = []
        attempted = failed = stripes_back = 0
        next_op = 0
        t_start = clock.monotonic()
        t_end = t_start + seconds
        now = t_start
        while now < t_end:
            if free:
                with span("bench.submit"):
                    while free:
                        slot = free.pop()
                        next_op, ok = self._submit(slot, next_op)
                        attempted += 1
                        if not ok:
                            # refused at the door: the slot retries
                            # after the next poll
                            failed += 1
                            free.append(slot)
                            break
            with span("bench.poll"):
                results = b.poll(q)
            if results:
                with span("bench.collect"):
                    stripes_back += len(results)
                    for t_sub, t_done, slot in self._collect(results):
                        lat.append(t_done - t_sub)
                        free.append(slot)
            else:
                wake = b.next_wakeup()
                if wake is not None:
                    with span("bench.wait"):
                        time.sleep(max(0.0, min(wake, t_end)
                                       - clock.monotonic()))
            now = clock.monotonic()
        elapsed = now - t_start
        self._drain()
        lat = np.sort(np.asarray(lat))
        p99 = lat[max(0, int(np.ceil(0.99 * len(lat))) - 1)] if len(lat) \
            else float("nan")
        return Window(
            seconds=elapsed, attempted=attempted, failed=failed,
            e2e={"ec_gbps": len(lat) * self.object_bytes / elapsed / 1e9,
                 "op_p99_ms": float(p99) * 1e3},
            layer={"least_bytes": stripes_back * self.least,
                   "ops": len(lat), "stripes": stripes_back})

    def _submit(self, slot: int, i: int):
        obj = int(self.order[i % self.dataset])
        if i in self.sampled:
            self.outputs[i] = {}
            self.objs[i] = obj
        rec = [obj, self.batcher.clock.monotonic(), self.stripes, 0.0,
               slot]
        self.ops[i] = rec
        for s in range(self.stripes):
            r = self._request(obj, s)
            if not self.queue.submit(r):
                # the op fails; stripes already admitted come back
                # and are dropped
                rec[2] = -1
                self.outputs.pop(i, None)
                return i + 1, False
            self.req_op[r.req_id] = (i, s)
        return i + 1, True

    def _collect(self, results):
        done = []
        for res in results:
            i, s = self.req_op.pop(res.request.req_id)
            rec = self.ops[i]
            if i in self.outputs:
                self.outputs[i][s] = np.array(res.output)
            if rec[2] < 0:
                continue
            rec[2] -= 1
            rec[3] = max(rec[3], res.completed)
            if rec[2] == 0:
                del self.ops[i]
                done.append((rec[1], rec[3], rec[4]))
        return done

    def _drain(self) -> None:
        """Wait for every op still out after the close (late, not
        wrong); their answers feed only the comparison."""
        deadline = time.monotonic() + DRAIN_S
        while self.req_op and time.monotonic() < deadline:
            self._collect(self.batcher.poll(self.queue)
                          + self.batcher.flush())

    def release(self) -> None:
        del self.batcher, self.queue
        self.helpers = None
        gc.collect()

    # -- the comparison ----------------------------------------------------

    def check(self, reference=None):
        """Ops whose returned bytes differ from the plain reference's,
        or that never came back, among the sampled ops submitted."""
        parity_fn = reference or importlib.import_module(
            f"benchmark.reference.{self.plugin}").parity
        ops = sorted(self.outputs)
        objs = sorted({self.objs[i] for i in ops})
        truth = {}
        if objs:
            if self.op == "encode":
                need = objs
            else:
                need = [o for o in objs
                        if any(x >= self.k for x in self.lost_ids[o])]
            if need:
                par = parity_fn(self.data[need].reshape(
                    -1, self.k, self.chunk), self.profile).reshape(
                    len(need), self.stripes, -1, self.chunk)
            for o in objs:
                full = (np.concatenate([self.data[o], par[need.index(o)]],
                                       axis=1) if o in need
                        else self.data[o])
                truth[o] = (full[:, self.k:] if self.op == "encode"
                            else full[:, list(self.lost_ids[o])])
        wrong = 0
        for i in ops:
            got = self.outputs[i]
            if len(got) < self.stripes:
                wrong += 1
                continue
            out = np.stack([got[s] for s in range(self.stripes)])
            wrong += not np.array_equal(out, truth[self.objs[i]])
        print(f"compared {len(ops)} ops of {len(objs)} objects",
              file=sys.stderr, flush=True)
        return [Check("ops_wrong", wrong, 0)]
