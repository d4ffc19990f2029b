"""Darlin: delayed block proximal gradient for L1 logistic regression.

Counterpart of ``src/app/linear_method/darlin.h`` (the reference's batch
solver). Semantics preserved exactly:

- multiplicative dual ``dual_i = exp(y_i · Xw_i)``, ``τ_i = 1/(1+dual_i)``;
- per-block first-order gradient ``G_j = Σ_i −y_i τ_i x_ij`` and
  second-order upper bound
  ``U_j = Σ_i min(τ(1−τ)·e^{|x_ij|·δ_j}, ¼)·x_ij²`` (binary features use
  ``e^{δ_j}``), ref ComputeGradient (darlin.h:417-462);
- server shrink step with trust region ``δ`` and KKT filter / active set,
  ref UpdateWeight (darlin.h:261-306): suspended coordinates are skipped
  until ``reset_kkt_filter``;
- ``Δ(δmax, d) = min(δmax, 2|d| + 0.1)`` (darlin.h:174);
- dual update ``dual_i *= exp(y_i · x_ij · d_j)``, ref UpdateDual;
- scheduler loop with randomized block order, bounded block delay τ, KKT
  threshold annealing ``thr = violation/num_ex · ratio`` and the
  reset-on-converge double-check, ref DarlinScheduler::Run.

TPU mapping: examples are sharded over the data axis (dual lives sharded);
block weights/δ/active-set are replicated (blocks are small); per-block
G/U are segment-sums over static-shape COO column blocks followed by a
psum over the data axis — that psum IS the worker→server gradient push of
the reference, and the broadcasted shrink result IS the server→worker
weight pull.

Bounded delay τ (ref darlin.h AddWaitTime / Submit with wait ≤ τ): block
steps are submitted through the Executor with a dependency on step
``ts − τ − 1``, so up to τ+1 block updates are in flight. All block state
(w/δ/active per block, the dual) stays device-resident; the host never
blocks on a step's result inside a pass, it only waits for the bounded-
delay horizon — XLA's async dispatch pipelines the queued steps while the
host prepares the next submissions, reproducing the reference's overlap
of block compute with communication.
"""

from __future__ import annotations

import dataclasses
import random
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...utils import file as psfile

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ...learner.bcd import BCDProgress, BCDScheduler, FeatureBlock
from ...parallel import mesh as meshlib
from ...parallel.mesh import DATA_AXIS
from ...utils.sparse import SparseBatch
from .config import BCDConfig, Config


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ColBlock:
    """Static-shape CSC column block, example rows sharded over data axis."""

    rows: np.ndarray  # [D, NZ] int32 — local example ids (rows_pad sentinel)
    cols: np.ndarray  # [D, NZ] int32 — block-local column ids
    vals: np.ndarray  # [D, NZ] float32 (0 ⇒ padding)
    num_cols: int = dataclasses.field(metadata={"static": True})


def _pow2_bucket(n: int, floor: int = 1024) -> int:
    b = floor
    while b < n:
        b <<= 1
    return b


class DarlinSolver:
    """Fused worker+server for one darlin run (ref DarlinWorker+DarlinServer)."""

    def __init__(self, conf: Config, mesh=None):
        from ...system.postoffice import Postoffice

        self.conf = conf
        self.bcd: BCDConfig = conf.darlin or BCDConfig()
        self.mesh = mesh if mesh is not None else Postoffice.instance().mesh
        assert self.mesh is not None, "Postoffice.start() first"
        self.lam = float(conf.penalty.lambda_[0])
        self.eta = float(conf.learning_rate.alpha)
        self.n_workers = meshlib.num_workers(self.mesh)
        self._block_steps: Dict[Tuple[int, int], object] = {}
        # device state, set by init_data
        self.y: Optional[jax.Array] = None
        self.dual: Optional[jax.Array] = None
        self.row_mask: Optional[jax.Array] = None
        # per-block device-resident model state (jax arrays) — the host
        # never syncs on these inside a pass (τ-delay pipelining)
        self.w_blk: List[jax.Array] = []
        self.delta_blk: List[jax.Array] = []
        self.active_blk: List[jax.Array] = []
        self.fea_blocks: List[FeatureBlock] = []
        self.blocks: List[ColBlock] = []
        self.num_ex = 0
        self.num_cols = 0
        self.rows_per_shard = 0

    # -- preprocessing (ref BCDWorker::PreprocessData) --

    def init_data(self, data: SparseBatch, fea_blocks: List[FeatureBlock]) -> None:
        n = data.n
        d = self.n_workers
        per = -(-n // d)
        self.rows_per_shard = per
        self.num_ex = n
        y = np.zeros((d, per), np.float32)
        mask = np.zeros((d, per), np.float32)
        for s in range(d):
            lo, hi = min(s * per, n), min((s + 1) * per, n)
            y[s, : hi - lo] = data.y[lo:hi]
            mask[s, : hi - lo] = 1.0
        batch_sh = NamedSharding(self.mesh, P(DATA_AXIS))
        self.y = jax.device_put(jnp.asarray(y), batch_sh)
        self.row_mask = jax.device_put(jnp.asarray(mask), batch_sh)
        self.dual = jax.device_put(jnp.ones((d, per), jnp.float32), batch_sh)

        f = data.cols
        self.num_cols = f
        self.fea_blocks = list(fea_blocks)
        self.w_blk, self.delta_blk, self.active_blk = [], [], []
        for blk in fea_blocks:
            c = blk.col_range.size()
            self.w_blk.append(jnp.zeros(c, jnp.float32))
            self.delta_blk.append(
                jnp.full(c, self.bcd.delta_init_value, jnp.float32)
            )
            self.active_blk.append(jnp.ones(c, bool))

        # build per-block static COO (cols local to block, rows local to shard)
        csc = data.to_csc()
        rows_global = csc.row_ids
        vals_global = csc.values
        self.blocks = []
        for blk in fea_blocks:
            c0, c1 = blk.col_range.begin, blk.col_range.end
            lo, hi = csc.colptr[c0], csc.colptr[c1]
            cols_rep = np.repeat(
                np.arange(c1 - c0, dtype=np.int32),
                np.diff(csc.colptr[c0 : c1 + 1]).astype(np.int64),
            )
            rows_blk = rows_global[lo:hi]
            vals_blk = (
                np.ones(hi - lo, np.float32) if vals_global is None else vals_global[lo:hi]
            )
            # split by example shard
            shard_ids = np.minimum(rows_blk // per, d - 1)
            nz_pad = _pow2_bucket(int(np.bincount(shard_ids, minlength=d).max()) if hi > lo else 1)
            rows_arr = np.zeros((d, nz_pad), np.int32)
            cols_arr = np.zeros((d, nz_pad), np.int32)
            vals_arr = np.zeros((d, nz_pad), np.float32)
            for s in range(d):
                sel = shard_ids == s
                k = int(sel.sum())
                rows_arr[s, :k] = rows_blk[sel] - s * per
                cols_arr[s, :k] = cols_rep[sel]
                vals_arr[s, :k] = vals_blk[sel]
            self.blocks.append(
                ColBlock(rows=rows_arr, cols=cols_arr, vals=vals_arr, num_cols=c1 - c0)
            )

    # -- the fused per-block device step --

    def _get_step(self, num_cols: int, nz_pad: int):
        key = (num_cols, nz_pad)
        if key in self._block_steps:
            return self._block_steps[key]
        lam, eta = self.lam, self.eta
        delta_max = self.bcd.delta_max_value
        rows_per = self.rows_per_shard

        def local(w, delta, active, dual, y, mask, rows, cols, vals, thr, reset):
            y, mask, dual = y[0], mask[0], dual[0]
            rows, cols, vals = rows[0], cols[0], vals[0]
            active = jnp.where(reset > 0, jnp.ones_like(active), active)

            tau = 1.0 / (1.0 + dual)  # [R]
            tr = tau[rows]
            yr = y[rows]
            # G_j and U_j (ref ComputeGradient): padding vals=0 contribute 0
            g_col = jax.ops.segment_sum(-yr * tr * vals, cols, num_segments=num_cols)
            d_col = delta  # [C] block-local
            curv = jnp.minimum(
                tr * (1 - tr) * jnp.exp(jnp.abs(vals) * d_col[cols]), 0.25
            )
            u_col = jax.ops.segment_sum(curv * vals * vals, cols, num_segments=num_cols)
            g_col = jax.lax.psum(g_col, DATA_AXIS)  # the gradient push
            u_col = jax.lax.psum(u_col, DATA_AXIS)

            # server shrink update (ref UpdateWeight)
            u = u_col / eta + 1e-10
            g_pos = g_col + lam
            g_neg = g_col - lam
            w_zero = w == 0
            vio = jnp.where(
                w_zero & active,
                jnp.where(g_pos < 0, -g_pos, jnp.where(g_neg > 0, g_neg, 0.0)),
                0.0,
            )
            violation = jnp.max(vio)
            deactivate = w_zero & active & (g_pos > thr) & (g_neg < -thr) & (vio == 0)
            new_active = active & ~deactivate

            d_w = jnp.where(
                g_pos <= u * w, -g_pos / u, jnp.where(g_neg >= u * w, -g_neg / u, -w)
            )
            d_w = jnp.clip(d_w, -delta, delta)
            d_w = jnp.where(new_active, d_w, 0.0)
            new_delta = jnp.where(
                new_active, jnp.minimum(delta_max, 2.0 * jnp.abs(d_w) + 0.1), delta
            )
            new_w = w + d_w

            # dual update (ref UpdateDual): dual *= exp(y * x * d_w)
            xdw = jax.ops.segment_sum(vals * d_w[cols], rows, num_segments=rows_per)
            new_dual = dual * jnp.exp(y * xdw) * mask + (1 - mask)

            return new_w, new_delta, new_active, new_dual[None, :], violation

        batch_spec = P(DATA_AXIS)

        @jax.jit
        def step(w, delta, active, dual, y, mask, rows, cols, vals, thr, reset):
            return shard_map(
                local,
                mesh=self.mesh,
                in_specs=(
                    P(), P(), P(),
                    batch_spec, batch_spec, batch_spec,
                    batch_spec, batch_spec, batch_spec,
                    P(), P(),
                ),
                out_specs=(P(), P(), P(), batch_spec, P()),
                check_vma=False,
            )(w, delta, active, dual, y, mask, rows, cols, vals, thr, reset)

        self._block_steps[key] = step
        return step

    def dispatch_block(self, blk_id: int, thr: float, reset: bool) -> jax.Array:
        """Dispatch one block update WITHOUT host sync; returns the block's
        KKT violation as an async device scalar (ref Submit(UPDATE_MODEL)).

        The new block state replaces the device references immediately —
        XLA's dependency tracking chains consecutive steps through the
        shared dual, so program order is preserved while the host runs
        ahead (bounded by the scheduler's τ horizon)."""
        data = self.blocks[blk_id]
        step = self._get_step(data.num_cols, data.vals.shape[-1])
        new_w, new_delta, new_active, new_dual, violation = step(
            self.w_blk[blk_id],
            self.delta_blk[blk_id],
            self.active_blk[blk_id],
            self.dual,
            self.y,
            self.row_mask,
            data.rows,
            data.cols,
            data.vals,
            jnp.float32(thr),
            jnp.int32(1 if reset else 0),
        )
        self.w_blk[blk_id] = new_w
        self.delta_blk[blk_id] = new_delta
        self.active_blk[blk_id] = new_active
        self.dual = new_dual
        return violation

    def update_block(
        self, blk_id: int, fea_blocks: List[FeatureBlock], thr: float, reset: bool
    ) -> float:
        """Synchronous single-block update (parity tests / debugging)."""
        del fea_blocks  # block geometry is fixed at init_data
        return float(self.dispatch_block(blk_id, thr, reset))

    def reset_active(self) -> None:
        """Re-activate every coordinate (ref reset_kkt_filter → fill(true))."""
        self.active_blk = [jnp.ones_like(a) for a in self.active_blk]

    # -- host views of the device-resident model (materialize on demand) --

    def _assemble(self, parts: List[jax.Array], fill, dtype) -> np.ndarray:
        out = np.full(self.num_cols, fill, dtype)
        for blk, p in zip(self.fea_blocks, parts):
            out[blk.col_range.begin : blk.col_range.end] = np.asarray(p)
        return out

    @property
    def w(self) -> np.ndarray:
        return self._assemble(self.w_blk, 0.0, np.float32)

    @property
    def delta(self) -> np.ndarray:
        return self._assemble(self.delta_blk, self.bcd.delta_init_value, np.float32)

    @property
    def active(self) -> np.ndarray:
        return self._assemble(self.active_blk, True, bool)

    # -- evaluation (ref DarlinServer::Evaluate + worker objective) --

    def evaluate(self) -> BCDProgress:
        # objective = sum log(1+exp(-y Xw)) + λ|w|_1; dual = exp(y Xw)
        dual = np.asarray(self.dual)
        mask = np.asarray(self.row_mask) > 0
        logloss = float(np.log1p(1.0 / dual[mask]).sum())
        w = self.w  # materialize the device blocks once
        return BCDProgress(
            objective=logloss + self.lam * float(np.abs(w).sum()),
            nnz_w=int((w != 0).sum()),
            nnz_active_set=int(self.active.sum()),
        )

    def predict_margin(self) -> np.ndarray:
        """Xw for the training examples, from the dual (exp(y·Xw))."""
        dual = np.asarray(self.dual)
        y = np.asarray(self.y)
        mask = np.asarray(self.row_mask) > 0
        return (np.log(dual[mask]) / np.where(y[mask] != 0, y[mask], 1.0)).ravel()


class DarlinScheduler(BCDScheduler):
    """ref DarlinScheduler::Run — the full training loop."""

    def __init__(self, conf: Config, mesh=None, name: str = "darlin_scheduler"):
        super().__init__(conf.darlin or BCDConfig(), name=name)
        self.conf = conf
        # comm_filter parity (ref bcd.conf): KEY_CACHING is structurally
        # subsumed — feature blocks stay device-resident across passes, so
        # keys are never resent at all; other filter types would change
        # numerics and warn rather than silently no-op
        import logging

        for f in (conf.darlin.comm_filter if conf.darlin else []) or []:
            ftype = str(f.get("type", "") if isinstance(f, dict) else f).lower()
            if ftype not in ("key_caching", "compressing"):
                logging.getLogger(__name__).warning(
                    "darlin comm_filter %r is not applied (blocks are "
                    "device-resident; only key_caching/compressing "
                    "semantics are subsumed)", ftype,
                )
        self.solver = DarlinSolver(conf, mesh=mesh)
        self.seed = 0
        self._converged_once = False
        # τ-delay instrumentation. max_dispatch_window counts steps the host
        # submitted without waiting for completion (the bounded-delay window
        # the scheduler is ALLOWED to run ahead — deterministic, = τ+1 when
        # enough blocks exist). max_in_flight_observed probes jax.Array
        # .is_ready() at submit time: steps whose device computation had
        # genuinely not finished yet (timing-dependent; reported, the window
        # is what tests assert on).
        self.max_dispatch_window = 0
        self.max_in_flight_observed = 0

    def run_on(self, data: SparseBatch, verbose: bool = False) -> BCDProgress:
        self.set_data(data)
        return self.run_loaded(verbose=verbose)

    def run_loaded(self, verbose: bool = False) -> BCDProgress:
        """Train on already-loaded/localized data (after load_data)."""
        assert self.conf.loss.type == "logit", "darlin trains l1-logit"
        assert self.conf.penalty.type == "l1"
        assert self.data is not None, "load data first"
        localized = self.data
        blocks = self.divide_feature_blocks()
        self.solver.init_data(localized, blocks)

        from ...system.executor import Executor

        # bounded block delay τ (ref darlin.h AddWaitTime: step ts waits on
        # everything up to ts − τ − 1, so ≤ τ+1 block tasks are in flight)
        tau = max(0, self.bcd_conf.max_block_delay)
        executor = Executor(name=self.name)
        rng = random.Random(self.seed)
        try:
            return self._run_passes(executor, tau, rng, verbose)
        finally:
            executor.stop()

    def _run_passes(self, executor, tau, rng, verbose) -> BCDProgress:
        from ...system.message import Task

        kkt_threshold = 1e20
        reset_kkt = False
        prev_objv = None
        prog = BCDProgress()
        for iteration in range(self.bcd_conf.num_data_pass):
            order = list(self.blk_order)
            if self.bcd_conf.random_feature_block_order:
                rng.shuffle(order)
            if reset_kkt:
                # reference resets the active set for ALL groups
                # (darlin.h Update: reset_kkt_filter -> fill(true) per grp)
                self.solver.reset_active()
                reset_kkt = False
            pass_start = executor.time()
            pending_ts = []
            for blk_id in order:
                dep = executor.time() - (tau + 1)
                task = Task(wait_time=[dep] if dep >= pass_start else [])
                ts = executor.submit(
                    lambda b=blk_id, t=kkt_threshold: self.solver.dispatch_block(
                        b, t, reset=False
                    ),
                    task,
                )
                pending_ts.append(ts)
                # probe genuine device-side concurrency: dispatched steps
                # whose violation scalars have not materialized yet
                probe = 0
                for t in pending_ts:
                    v = executor.result(t)
                    if v is not None and hasattr(v, "is_ready") and not v.is_ready():
                        probe += 1
                self.max_in_flight_observed = max(
                    self.max_in_flight_observed, probe
                )
            self.po.beat(self.name)  # liveness signal (ref heartbeat thread)
            vios = [executor.wait(t) for t in pending_ts]
            self.max_dispatch_window = max(
                self.max_dispatch_window, executor.max_dispatched_in_flight
            )
            violation = max(
                (float(v) for v in vios if v is not None), default=0.0
            )
            prog = self.solver.evaluate()
            prog.violation = violation
            if prev_objv is not None and prev_objv > 0:
                prog.relative_obj = (prev_objv - prog.objective) / prev_objv
            self.merge_progress(iteration, prog)
            if verbose:
                print(self.show_progress(iteration))
            # KKT threshold annealing (ref Run: vio/num_ex*ratio)
            kkt_threshold = (
                violation / max(1, self.solver.num_ex)
                * self.bcd_conf.kkt_filter_threshold_ratio
            )
            rel = prog.relative_obj
            if prev_objv is not None and 0 <= rel <= self.bcd_conf.epsilon:
                if reset_kkt is False and self._converged_once:
                    break
                self._converged_once = True
                reset_kkt = True  # double-check with full active set
            else:
                self._converged_once = False
            prev_objv = prog.objective
        return prog

    def save_model(self, path: str) -> List[str]:
        """key\\tweight text dump, one file per server shard named
        ``{path}_S{k}`` (ref BCDServer::SaveModel → WriteToFile with
        ``file + "_" + MyNodeID()``; eval configs match ``model_S.*``).
        Shards take contiguous key ranges (Range::EvenDivide)."""
        keys = self.global_keys
        w = self.solver.w
        n_server = meshlib.num_servers(self.solver.mesh)
        bounds = [len(keys) * s // n_server for s in range(n_server + 1)]
        written = []
        for s in range(n_server):
            spath = f"{path}_S{s}"
            with psfile.open_write(spath) as f:
                for i in range(bounds[s], bounds[s + 1]):
                    v = w[i]
                    if v != 0 and not np.isnan(v):
                        f.write(f"{keys[i]}\t{float(v)!r}\n")
            written.append(spath)
        return written
