"""The runner of ``apps/linear``: the Criteo FTRL trainer under the
harness's window (README.md, "The runner's contract").

What ``run.py`` held for this application until PR 28, moved here as it
was: the configuration's ``.conf`` as it is run, the Criteo text file
made from ``--seed``, Postoffice, scheduler, worker and reader as
``apps/linear/main.py`` builds them, the window's two calls per launch
wrapped around ``worker._submit_prepped`` and ``worker.collect`` on the
instance (the program's code is untouched), the feed, the FTRL checks
against ``oracle.py``, and the readers' ``conf`` and
``ministeps_per_launch``.

An example is one Criteo row; a launch is ``steps_per_launch``
minibatches dispatched as one program.
"""

from __future__ import annotations

import itertools
import os
import re
import time

from chipbench import oracle, synth
from chipbench.readers import registry_delta


class Runner:
    def __init__(self, run):
        self.run = run  # run.py's Run: the cell's files, --seed, note()
        self.first = []  # the first minibatches fed, for the oracle
        self.fed = self.batches = 0

    # -- set-up ------------------------------------------------------------

    def conf_text(self) -> str:
        """The configuration's ``.conf`` as it is run. A rehearsal swaps
        the sizes the configuration's file lists for toy ones, same
        keys."""
        cfg = self.run.cfg
        with open(os.path.join(self.run.root, cfg["conf"])) as f:
            text = f.read()
        if self.run.rehearsal:
            for key, value in cfg["rehearsal"]["conf"].items():
                text, n = re.subn(
                    rf"(?m)^(\s*{key}:\s*)\S+", rf"\g<1>{value}", text
                )
                if n != 1:
                    raise ValueError(f"{cfg['conf']}: {n} lines set {key}")
        return text

    def make_data(self) -> None:
        """The cell's file, made from ``--seed`` and kept by seed, so
        that only the first run with a seed generates it."""
        from parameter_server_tpu.data.text_parser import ExampleParser

        mix = self.run.mix
        if not ExampleParser(mix["format"]).use_native:
            raise RuntimeError(
                f"format {mix['format']!r} would take the Python line path"
            )
        self.data = os.path.join(
            self.run.cache, "data",
            f"{self.run.cell['traffic']}.r{mix['rows']}"
            f".v{mix['vocabulary']}.s{self.run.seed}.txt",
        )
        if not os.path.exists(self.data):
            synth.write_criteo_file(
                self.data, mix["rows"], mix["vocabulary"], self.run.seed
            )

    def build(self, win) -> None:
        """Postoffice, scheduler, worker and reader as
        ``apps/linear/main.py`` builds them, from the benchmark's copy of
        the conf; then the window's calls around the worker's two."""
        from parameter_server_tpu.apps.linear.async_sgd import (
            AsyncSGDScheduler,
            AsyncSGDWorker,
        )
        from parameter_server_tpu.apps.linear.config import parse_conf
        from parameter_server_tpu.learner.sgd import MinibatchReader
        from parameter_server_tpu.system.postoffice import Postoffice

        cfg, mix = self.run.cfg, self.run.mix
        self.conf = conf = parse_conf(self.conf_text())
        sgd = conf.async_sgd
        if not self.run.rehearsal:
            # the configuration's file states the sizes; the .conf runs them
            differ = {k: (v, getattr(sgd, k))
                      for k, v in cfg["async_sgd"].items()
                      if getattr(sgd, k) != v}
            if differ:
                raise ValueError(
                    f"{self.run.entry['file']} and {cfg['conf']}: {differ}"
                )
        self.po = Postoffice.instance().start(**cfg["mesh"])
        # main.py's --heartbeat-timeout, set as an operator of these tables
        # would: a worker beats only in collect(), and a cold compile of the
        # step (18 s at 2^29) sits inside one
        aux = self.po.start_aux(heartbeat_timeout=120.0)
        aux.start(check_interval=2.0, dashboard_interval=0.0)
        sched = AsyncSGDScheduler(conf)
        sched.run()
        self.worker = worker = AsyncSGDWorker(conf)
        worker.attach_monitor(sched)
        aux.register(worker.name)
        self.reader = MinibatchReader(
            # one file reread in passes. The reader globs every entry, and a
            # stat is dear in the chip machine's sandbox: 65,536 entries took
            # 11 s of every set-up
            files=[self.data] * 1024,
            minibatch_size=sgd.minibatch,
            data_format=mix["format"],
        )
        if sgd.tail_feature_freq > 0:
            self.reader.init_filter(
                sgd.countmin_n, sgd.countmin_k, sgd.tail_feature_freq
            )
        self.T = max(1, sgd.steps_per_launch)
        self._wrap(win)

    def _wrap(self, win) -> None:
        """Tell the window of each launch, from around the trainer's two
        calls per launch."""
        worker, by_ts = self.worker, {}
        submit, collect = worker._submit_prepped, worker.collect
        # the worker's running totals, not the record collect() returns:
        # the scheduler's progress printer empties that one
        total = worker.progress

        def timed_submit(prepped, **kw):
            row = win.submitted()
            ts = submit(prepped, **kw)
            by_ts[ts] = row
            return ts

        def timed_collect(ts):
            n0, k0 = total.num_examples_processed, len(total.objective)
            prog = collect(ts)
            win.collected(
                by_ts.pop(ts),
                examples=total.num_examples_processed - n0,
                objective=sum(total.objective[k0:]),
            )
            return prog

        worker._submit_prepped = timed_submit
        worker.collect = timed_collect
        self.win = win

    # -- warm-up and window ------------------------------------------------

    def _feed(self, source):
        """What the trainer reads. Runs on the ingest feeder thread; ends
        at the first launch boundary after the window's deadline."""
        for batch in source:
            if len(self.first) < self.run.mix["parity_minibatches"]:
                self.first.append(batch)
            self.batches += 1
            self.fed += batch.n
            yield batch
            if self.batches % self.T == 0 and self.win.expired():
                return

    def warm_up(self) -> None:
        """This cell's shapes and no others."""
        self.reader.start()
        try:
            self.source = self._feed(iter(self.reader))
            self.worker.train(itertools.islice(
                self.source, self.run.mix["warmup_launches"] * self.T
            ))
        except BaseException:
            self.reader.close()
            raise

    def feed(self) -> None:
        """Train until the window says stop, and drain."""
        try:
            self.worker.train(self.source)
        finally:
            self.reader.close()

    # -- evidence, checks, context -----------------------------------------

    def window_note(self) -> dict:
        return {
            "ministeps_per_launch": self.T,
            "data_passes": self.fed / self.run.mix["rows"],
        }

    def notes(self, win) -> None:
        counters = {"before": win.before, "after": win.after}
        stages = {
            stage: {
                name: registry_delta.read(counters, {
                    "metric": "ps_ingest_stage_seconds", "field": field,
                    "labels": {"stage": stage},
                })
                for name, field in (("s", "sum"), ("batches", "count"))
            }
            for stage in sorted({
                x["labels"]["stage"]
                for x in win.after["ps_ingest_stage_seconds"]["series"]
            })
        }
        self.run.note("ingest_stages_in_window", **stages)

    def checks(self, win, warm: list, rows: list, check) -> None:
        """FTRL's own part of ``correct``, each check printed with its
        numbers."""
        from parameter_server_tpu.telemetry import learning

        conf, cfg = self.conf, self.run.cfg
        sgd = conf.async_sgd
        t = time.perf_counter()
        dev_ll = sum(r["objective"] for r in warm) / sum(
            r["examples"] for r in warm
        )
        lambdas = list(conf.penalty.lambda_) + [0.0]
        ref_ll = oracle.progressive_logloss(
            self.first, sgd.num_slots, conf.learning_rate.alpha,
            conf.learning_rate.beta, lambdas[0], lambdas[1],
        )
        tol = max(0.01, 0.02 * ref_ll)
        check(
            "logloss_parity",
            len(warm) * self.T == len(self.first)
            and abs(dev_ll - ref_ll) <= tol,
            value=abs(dev_ll - ref_ll), limit=tol,
            device=dev_ll, oracle=ref_ll,
            minibatches=len(self.first), oracle_s=time.perf_counter() - t,
        )
        plane = learning.snapshot_all()[self.worker.name]
        st = plane["staleness"]
        tau = cfg["guarantees"]["max_delay"]
        check(
            "staleness_within_max_delay",
            sgd.max_delay == tau and st["observed_max"] <= tau
            and st["within_bound"],
            value=st["observed_max"], limit=tau,
            conf_max_delay=sgd.max_delay, live_tau=st.get("live_tau"),
        )
        check(
            "examples_confirmed", plane["examples"] == self.fed,
            value=plane["examples"], limit=self.fed,
        )
        paths = {
            s["labels"]["path"]: s["value"]
            for s in win.after["ps_ftrl_update_path_total"]["series"]
        }
        check(
            "update_path_on_device",
            paths and (self.run.rehearsal or not paths.get("ref")),
            value=paths.get("ref", 0.0), limit=0,
            ministeps_by_path=paths, note=cfg.get("update_path_today"),
        )
        deaths = registry_delta.total(
            win.after, {"metric": "ps_recovery_deaths_total"}
        )
        check("no_node_declared_dead", deaths == 0, value=deaths, limit=0)

    def ctx(self) -> dict:
        """The readers' keys that only this application has."""
        return {
            "ministeps_per_launch": self.T, "conf": self.conf.async_sgd,
        }

    def stop(self) -> None:
        self.po.stop()  # stops the aux runtime and the executors' threads
