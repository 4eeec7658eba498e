"""Decision units: training control (rebuild of ``znicz/decision.py``).

Runs once per minibatch, right after the evaluator.  Accumulates per-class
epoch statistics, and at epoch end (the loader's TRAIN tail):

  - tracks the best validation metric (n_err for GD, mse for MSE),
  - raises ``improved`` (the snapshotter's trigger),
  - raises ``complete`` when ``max_epochs`` is reached or validation hasn't
    improved for ``fail_iterations`` epochs,
  - maintains ``gd_skip`` — the Bool that gates every GD unit off for
    TEST/VALID minibatches (only TRAIN minibatches backprop; reference
    semantics).

Class indices follow the reference: TEST=0, VALID=1, TRAIN=2; the loader
serves one full pass over test, then valid, then train per epoch.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from znicz_tpu.core.mutable import Bool
from znicz_tpu.core.units import Unit
from znicz_tpu.loader.base import TEST, TRAIN, VALID
from znicz_tpu.memory import Array

CLASS_NAMES = ("test", "valid", "train")


class DecisionBase(Unit):
    def __init__(self, workflow=None, name=None, **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        self.max_epochs = kwargs.get("max_epochs", 10)
        #: epochs without validation improvement before stopping (0 = off)
        self.fail_iterations = kwargs.get("fail_iterations", 0)
        self.complete = Bool(False)
        self.improved = Bool(False)
        self.epoch_ended = Bool(False)
        self.gd_skip = Bool(False)
        # linked from loader:
        self.minibatch_class = TRAIN
        self.last_minibatch = False
        self.class_ended = False
        self.epoch_number = 0
        self.class_lengths: List[int] = [0, 0, 0]
        # linked from evaluator:
        self.minibatch_loss = 0.0
        # epoch accumulators / history
        self.epoch_metrics = [None, None, None]   # last finished epoch
        #: {class name: mean loss} of every finished epoch, oldest first
        self.epoch_history: List[dict] = []
        self._acc_loss = [0.0, 0.0, 0.0]
        self._acc_batches = [0, 0, 0]
        self.best_metric = np.inf
        self.best_epoch = -1
        self._fails = 0
        self.on_epoch_end = []                    # callbacks(decision)
        # telemetry (ISSUE 5): the decision loop's live state as
        # collect-time gauges — zero hot-path writes, the scrape reads
        # the attributes this unit already maintains.  weak_fn: the
        # process-global registry must not pin the decision (and the
        # whole workflow graph behind its links) after the run
        from znicz_tpu import telemetry

        _sc = telemetry.scope("decision")
        _sc.gauge("epoch_number", "current epoch",
                  fn=telemetry.weak_fn(
                      self, lambda d: float(d.epoch_number)))
        _sc.gauge("best_metric", "best validation metric so far",
                  fn=telemetry.weak_fn(
                      self, lambda d: float(d.best_metric)))
        _sc.gauge("train_complete", "1 once training stopped",
                  fn=telemetry.weak_fn(
                      self, lambda d: float(bool(d.complete))))

    # -- metric plumbing (subclasses refine) ----------------------------------

    def _accumulate(self, klass: int) -> None:
        self._acc_loss[klass] += float(self.minibatch_loss)
        self._acc_batches[klass] += 1

    def _class_metric(self, klass: int) -> float:
        b = max(1, self._acc_batches[klass])
        return self._acc_loss[klass] / b

    def _reset_class(self, klass: int) -> None:
        self._acc_loss[klass] = 0.0
        self._acc_batches[klass] = 0

    def _validation_class(self) -> int:
        """Improvement is judged on VALID if present, else TRAIN."""
        return VALID if self.class_lengths[VALID] else TRAIN

    def improvement_metric(self) -> float:
        return self._class_metric(self._validation_class())

    # -- the stop rule ---------------------------------------------------------

    def _rule(self, epoch_number: int, improved: Optional[bool] = None):
        """``(improved, fails, done)`` of an epoch that ends now with the
        metric accumulated so far (or with ``improved`` as given): the one
        home of the stop rule.  ``run()`` adopts it at the epoch's tail;
        ``tail_stops()`` asks it ahead.  Reads, changes nothing."""
        if improved is None:
            improved = self.improvement_metric() < self.best_metric - 1e-12
        fails = 0 if improved else self._fails + 1
        done = bool(epoch_number + 1 >= self.max_epochs or
                    (self.fail_iterations and
                     fails >= self.fail_iterations))
        return improved, fails, done

    def tail_stops(self, epoch_number: int,
                   validated: bool = True) -> Optional[bool]:
        """Asked BEFORE the tail of epoch ``epoch_number`` is fed: will
        ``run()`` raise ``complete`` there?  ``False`` the run goes on (the
        tail's update will be adopted), ``True`` it stops, ``None`` cannot
        say: improvement is judged on TRAIN, so the tail's own loss
        decides.  With a validation set the answer is exact once this
        epoch's VALID minibatches are fed — the loader serves them before
        TRAIN and no TRAIN minibatch moves what the rule reads.  Asked
        earlier still (``validated`` False: VALID minibatches of this
        epoch are yet to be fed) it answers where the rule says the same
        whichever way the metric falls, as it does with no
        ``fail_iterations``.  A stop that an ``on_epoch_end`` callback
        asks for is not the rule's and cannot be seen here."""
        if self._validation_class() == TRAIN:
            return None
        if validated:
            return self._rule(epoch_number)[2]
        either = {self._rule(epoch_number, improved)[2]
                  for improved in (True, False)}
        return either.pop() if len(either) == 1 else None

    # -- run ------------------------------------------------------------------

    def run(self):
        klass = int(self.minibatch_class)
        self._accumulate(klass)
        self.epoch_ended.set(False)
        if self.class_ended:
            self.epoch_metrics[klass] = self._summarize(klass)
        if self.last_minibatch:            # end of TRAIN == end of epoch
            improved, self._fails, done = self._rule(self.epoch_number)
            if improved:
                self.best_metric = self.improvement_metric()
                self.best_epoch = int(self.epoch_number)
            self.improved.set(improved)
            self.complete.set(done)
            self.epoch_ended.set(True)
            self.epoch_history.append(
                {CLASS_NAMES[k]: self.epoch_metrics[k]["loss"]
                 for k in (TEST, VALID, TRAIN)
                 if self.class_lengths[k] and self.epoch_metrics[k]})
            self._log_epoch()
            for cb in self.on_epoch_end:
                cb(self)
            for k in (TEST, VALID, TRAIN):
                self._reset_class(k)
        # GD units run only on TRAIN minibatches while not complete.
        self.gd_skip.set(klass != TRAIN or bool(self.complete))

    def _summarize(self, klass: int):
        return {"loss": self._class_metric(klass)}

    def _log_epoch(self):
        parts = []
        for k in (TEST, VALID, TRAIN):
            if self.class_lengths[k] and self.epoch_metrics[k] is not None:
                m = self.epoch_metrics[k]
                stats = ", ".join(
                    f"{key}={val:.6g}" if isinstance(val, float)
                    else f"{key}={val}"
                    for key, val in m.items()
                    if isinstance(val, (int, float)) and key != "confusion")
                parts.append(f"{CLASS_NAMES[k]}: {stats}")
        self.info("epoch %d  %s%s", self.epoch_number, "  ".join(parts),
                  "  *" if bool(self.improved) else "")


class DecisionGD(DecisionBase):
    """Classification: tracks n_err% per class + confusion matrix; judges
    improvement on validation error count."""

    def __init__(self, workflow=None, name=None, **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        # linked from evaluator:
        self.minibatch_n_err = 0
        self.confusion_matrix = None
        self.max_err_output_sum = 0.0
        self._acc_n_err = [0, 0, 0]
        self._acc_samples = [0, 0, 0]
        self._acc_confusion: List[Optional[np.ndarray]] = [None, None, None]
        self.minibatch_size = 0

    def _accumulate(self, klass: int) -> None:
        super()._accumulate(klass)
        self._acc_n_err[klass] += int(self.minibatch_n_err)
        self._acc_samples[klass] += int(self.minibatch_size)
        if self.confusion_matrix is not None:
            conf = self.confusion_matrix
            if isinstance(conf, Array):        # unit path: evaluator Array
                conf = np.asarray(conf.map_read())
            # size<=1 is the evaluator's confusion-disabled sentinel
            # (wide heads skip the (C,C) reporting transfer)
            if conf.size > 1:
                # deliberately NOT np.asarray'd: the fused path feeds
                # device-resident matrices, and `+` keeps the running sum
                # on device — the (C,C) transfer happens only when a
                # consumer (plotter/report/test) actually reads the epoch
                # metric, so wide heads cost nothing per epoch on slow
                # host links (VERDICT r3 missing #4)
                if self._acc_confusion[klass] is None:
                    self._acc_confusion[klass] = conf.copy()
                else:
                    self._acc_confusion[klass] = \
                        self._acc_confusion[klass] + conf

    def _reset_class(self, klass: int) -> None:
        super()._reset_class(klass)
        self._acc_n_err[klass] = 0
        self._acc_samples[klass] = 0
        self._acc_confusion[klass] = None

    def improvement_metric(self) -> float:
        k = self._validation_class()
        return self._acc_n_err[k] / max(1, self._acc_samples[k])

    def _summarize(self, klass: int):
        n = max(1, self._acc_samples[klass])
        return {"loss": self._class_metric(klass),
                "n_err": self._acc_n_err[klass],
                "err_pct": 100.0 * self._acc_n_err[klass] / n,
                "confusion": self._acc_confusion[klass]}


class DecisionMSE(DecisionBase):
    """Regression/autoencoder: improvement on validation mean loss."""

    def _summarize(self, klass: int):
        return {"loss": self._class_metric(klass),
                "mse": self._class_metric(klass)}
