"""The served driver: concurrent tracking sessions through the program's
request plane (``ParticleFrontend`` over the server that the
configuration's ``build_server`` makes), fed by the open-loop schedule of
``bench/openloop.py``; every frame is timed from its due time to the
resolution of its future.

Each slot carries one stream at a time, a seeded movie of the mix's
``frames`` frames; when it ends, the stream closes and a new one opens in
the slot.  The window opens with a fresh stream in every slot.
"""
from __future__ import annotations

import asyncio
import sys

import jax
import numpy as np

from bench import harness, openloop
from bench.window import Span, Window


class Driver:
    """Tracking sessions behind the request plane under open-loop load."""

    CONTROL_WINDOW = True    # the control takes the place of served streams

    def __init__(self, cell: harness.Cell, seed: int, seconds: float):
        self.cell = cell
        self.t = cell.traffic
        self.seed = seed
        self.seconds = seconds
        self.loop = asyncio.new_event_loop()

    # -- set-up -------------------------------------------------------------
    def setup(self) -> None:
        """Every stream's frames and key, the server, all tier programs
        warmed, and one short burst through the whole request path."""
        from repro.serve import ParticleFrontend
        self.prepare(self.seed)
        server = self.cell.model.build_server(self.cell.config, self.t)
        self.fe = ParticleFrontend(server)
        self.loop.run_until_complete(self._warm())

    def prepare(self, seed: int, total_rate: float | None = None) -> None:
        """The arrival schedule (the mix's, or at ``total_rate``), and
        every stream's frames and PRNG key for ``seed``."""
        (self.s_inputs, self.s_keys, self.s_perm, _,
         self.s_ref, self.s_ctrl) = harness.sub_seeds(seed, 6)
        t = self.t
        self.dues = openloop.schedule(total_rate or t["total_rate"],
                                      t["slots"], t["zipf_s"], self.seconds,
                                      t["arrival_seed"], t.get("burst", 1))
        # the seed decides which slot carries which Zipf rank
        perm = np.random.default_rng(self.s_perm).permutation(t["slots"])
        self.slot_dues = [self.dues[r] for r in perm]
        n_streams = (openloop.streams_needed(self.slot_dues, t["frames"])
                     + t["slots"])
        obs, _ = self.cell.model.make_inputs(
            self.cell.config, t, jax.random.key(self.s_inputs), n_streams)
        self.movies = np.asarray(obs, np.float32)
        self.stream_keys = list(jax.random.split(
            jax.random.key(self.s_keys), n_streams))
        self.next_stream = t["slots"]

    async def _warm(self) -> None:
        fe = self.fe
        await fe.start()
        await fe.warmup(self.movies[0, 0])
        streams = [await fe.open(self.stream_keys[i])
                   for i in range(self.t["slots"])]
        for f in range(2):
            futs = [await fe.submit(s, self.movies[i, f])
                    for i, s in enumerate(streams)]
            await asyncio.gather(*futs)
        for s in streams:
            await fe.close(s)
        await fe.drain()

    # -- window -------------------------------------------------------------
    def window(self, seconds: float) -> Window:
        """Send every frame due in ``[0, seconds)`` at its due time; wait
        for each (``wait_s`` past the close at most)."""
        before = self.fe.snapshot()
        self.loop.run_until_complete(self._window())
        after = self.fe.snapshot()
        due = np.concatenate([r["due"] for r in self.records]) \
            if self.records else np.zeros(0)
        done = np.concatenate([r["done"] for r in self.records])
        sent = np.concatenate([r["sent"] for r in self.records])
        ok = np.isfinite(done)
        latency = np.where(ok, done - due, self.deadline - due)
        late = sent[np.isfinite(sent)] - due[np.isfinite(sent)]
        if late.size:
            print(f"generator lateness s: p50 {float(np.percentile(late, 50))!r}"
                  f" p99 {float(np.percentile(late, 99))!r} "
                  f"max {float(late.max())!r}",
                  file=sys.stderr, flush=True)
        counters = {}
        for name in ("frames", "steps"):
            counters[name] = (after["counters"].get(name, 0)
                              - before["counters"].get(name, 0))
        s_a = after["series"].get("step_ms", {"count": 0, "mean": 0.0})
        s_b = before["series"].get("step_ms", {"count": 0, "mean": 0.0})
        counters["step_ms_total"] = (s_a["mean"] * s_a["count"]
                                     - s_b["mean"] * s_b["count"])
        return Window({"frame_p95_ms": float(np.percentile(latency, 95)) * 1e3,
                       "frame_p50_ms": float(np.percentile(latency, 50)) * 1e3},
                      attempted=int(due.size), failed=int((~ok).sum()),
                      frames=int(ok.sum()), seconds=self.window_s,
                      counters=counters)

    async def _window(self) -> None:
        loop = asyncio.get_running_loop()
        self.records = []
        self.streams = []          # (movie index, results per frame)
        t0 = loop.time() + 0.01
        self.deadline = t0 + self.seconds + self.t["wait_s"]
        tasks = [asyncio.ensure_future(self._slot(t0, d))
                 for d in self.slot_dues]
        try:
            await asyncio.wait_for(asyncio.gather(*tasks),
                                   self.deadline - loop.time())
        except asyncio.TimeoutError:
            pass
        self.window_s = loop.time() - t0
        for r in self.records:
            r["due"] = r["due"] + t0

    async def _slot(self, t0: float, dues: np.ndarray) -> None:
        fe, k = self.fe, self.t["frames"]
        loop = asyncio.get_running_loop()
        rec = {"due": dues, "sent": np.full(len(dues), np.nan),
               "done": np.full(len(dues), np.inf)}
        self.records.append(rec)
        stream, futs = None, []
        for j, due in enumerate(dues):
            if stream is None or len(futs) == k:
                if stream is not None:
                    await self._finish(stream, futs)
                movie = self.next_stream
                self.next_stream += 1
                with Span("bench.open"):
                    stream = await fe.open(self.stream_keys[movie])
                futs = []
                results = {"movie": movie, "est": [], "ess": [], "lz": [],
                           "ok": []}
                self.streams.append(results)
            wait = t0 + due - loop.time()
            if wait > 0:
                await asyncio.sleep(wait)
            rec["sent"][j] = loop.time()
            with Span("bench.send"):
                fut = await fe.submit(stream, self.movies[movie, len(futs)])
            fut.add_done_callback(
                lambda f, j=j, res=results: self._resolved(f, rec, j, res))
            futs.append(fut)
        if stream is not None:
            await self._finish(stream, futs)

    def _resolved(self, fut, rec, j, results) -> None:
        rec["done"][j] = self.loop.time()
        if fut.cancelled() or fut.exception() is not None:
            rec["done"][j] = np.inf
            results["ok"].append(False)
            return
        r = fut.result()
        results["est"].append(np.asarray(r.estimate, np.float64))
        results["ess"].append(r.ess)
        results["lz"].append(r.log_marginal)
        results["ok"].append(True)

    async def _finish(self, stream, futs) -> None:
        await asyncio.gather(*futs, return_exceptions=True)
        with Span("bench.close"):
            await self.fe.close(stream)

    # -- after the window ---------------------------------------------------
    def release(self) -> None:
        """Stop the request plane and drop the server."""
        self.loop.run_until_complete(self.fe.stop())
        self.loop.close()
        self.fe = None

    def compare(self, control: bool = False) -> dict:
        """The worst of each compared number over every stream that was
        answered on at least the mix's ``min_frames`` frames, each stream
        against the reference over the frames it was sent, from frame
        ``warmup_frames`` on; plus the frames that were never answered.
        With ``control`` the reference in its control precision takes the
        place of the served results."""
        t, ref, cfg = self.t, self.cell.ref, self.cell.config
        unanswered = sum(int((~np.isfinite(r["done"])).sum())
                         for r in self.records)
        picked = [s for s in self.streams
                  if len(s["ok"]) >= t["min_frames"] and all(s["ok"])]
        values: dict = {"unanswered_frames": float(unanswered)}
        n = t["n_particles_per_session"]
        empty = (np.zeros((0, 5)), np.zeros(0), np.zeros(0))
        if not picked:
            values.update(ref.compare(cfg, t, empty, empty))
        for s in picked:
            # the reference runs over the whole movie, so that every
            # stream uses one compiled program; its first ``answered``
            # frames see only the frames the stream was sent
            answered = len(s["ok"])
            movie = self.movies[s["movie"]]
            got = (np.stack(s["est"]), np.asarray(s["ess"]),
                   np.asarray(s["lz"]))
            if control:
                got = ref.reference(cfg, t, movie, n, jax.random.fold_in(
                    jax.random.key(self.s_ctrl), s["movie"]),
                    dtype=ref.CONTROL)
            r = ref.reference(cfg, t, movie, n, jax.random.fold_in(
                jax.random.key(self.s_ref), s["movie"]))
            got, r = ([a[:answered] for a in x] for x in (got, r))
            for k, v in ref.compare(cfg, t, got, r).items():
                values[k] = max(values.get(k, -np.inf), v)
        values["compared_streams"] = float(len(picked))
        values["compared_frames"] = float(sum(
            len(s["ok"]) - t["warmup_frames"] for s in picked))
        return values
