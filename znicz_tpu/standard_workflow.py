"""StandardWorkflow: declarative model assembly (rebuild of
``znicz/standard_workflow.py``, SURVEY.md §2.2 / §3.1).

Builds the canonical training graph from a ``layers`` config list::

    layers = [
        {"type": "conv_relu", "->": {"n_kernels": 32, "kx": 5, "ky": 5,
                                     "padding": (2, 2, 2, 2)}},
        {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
        {"type": "all2all_tanh", "->": {"output_sample_shape": 100},
         "<-": {"learning_rate": 0.01}},
        {"type": "softmax", "->": {"output_sample_shape": 10}},
    ]

Per-layer dicts use the reference's arrow keys: ``"->"`` = forward-unit
kwargs, ``"<-"`` = backward(GD)-unit kwargs (per-layer lr/momentum/decay —
the semantics jax.grad would otherwise flatten away, SURVEY.md §1).

Wiring produced (identical to the reference's):
    start -> repeater -> loader -> fwd_0 .. fwd_n -> evaluator -> decision
    decision -> snapshotter -> gd_n .. gd_0 -> repeater
    decision.complete gates end_point; decision.gd_skip gates every gd;
    dropout/stochastic-pooling units get minibatch_class linked for their
    train/eval mode switch.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple, Type

from znicz_tpu.core.workflow import Repeater, Workflow
from znicz_tpu.decision import DecisionGD, DecisionMSE
from znicz_tpu.evaluator import EvaluatorMSE, EvaluatorSoftmax
from znicz_tpu.snapshotter import Snapshotter

# -- layer type registry ------------------------------------------------------


def _registry() -> Dict[str, Tuple[Type, Optional[Type]]]:
    from znicz_tpu import activation as act
    from znicz_tpu import all2all, conv, cutter, dropout, gd, gd_conv
    from znicz_tpu import gd_pooling, lrn, pooling

    reg: Dict[str, Tuple[Type, Optional[Type]]] = {
        "all2all": (all2all.All2All, gd.GradientDescent),
        "all2all_tanh": (all2all.All2AllTanh, gd.GDTanh),
        "all2all_relu": (all2all.All2AllRELU, gd.GDRELU),
        "all2all_strict_relu": (all2all.All2AllStrictRELU, gd.GDStrictRELU),
        "all2all_sigmoid": (all2all.All2AllSigmoid, gd.GDSigmoid),
        "softmax": (all2all.All2AllSoftmax, gd.GDSoftmax),
        "conv": (conv.Conv, gd_conv.GradientDescentConv),
        "conv_tanh": (conv.ConvTanh, gd_conv.GDTanhConv),
        "conv_relu": (conv.ConvRELU, gd_conv.GDRELUConv),
        "conv_strict_relu": (conv.ConvStrictRELU, gd_conv.GDStrictRELUConv),
        "max_pooling": (pooling.MaxPooling, gd_pooling.GDMaxPooling),
        "maxabs_pooling": (pooling.MaxAbsPooling, gd_pooling.GDMaxAbsPooling),
        "avg_pooling": (pooling.AvgPooling, gd_pooling.GDAvgPooling),
        "stochastic_pooling": (pooling.StochasticPooling,
                               gd_pooling.GDStochasticPooling),
        "stochastic_abs_pooling": (pooling.StochasticAbsPooling,
                                   gd_pooling.GDStochasticAbsPooling),
        "norm": (lrn.LRNormalizerForward, lrn.LRNormalizerBackward),
        "dropout": (dropout.DropoutForward, dropout.DropoutBackward),
        "cutter": (cutter.Cutter, cutter.GDCutter),
        "activation_tanh": (act.ForwardTanh, act.BackwardTanh),
        "activation_sigmoid": (act.ForwardSigmoid, act.BackwardSigmoid),
        "activation_relu": (act.ForwardRELU, act.BackwardRELU),
        "activation_str": (act.ForwardStrictRELU, act.BackwardStrictRELU),
        "activation_log": (act.ForwardLog, act.BackwardLog),
        "activation_sincos": (act.ForwardSinCos, act.BackwardSinCos),
        "activation_tanhlog": (act.ForwardTanhLog, act.BackwardTanhLog),
    }
    from znicz_tpu import attention, deconv, depooling, gd_deconv

    reg["deconv"] = (deconv.Deconv, gd_deconv.GDDeconv)
    reg["deconv_tanh"] = (deconv.DeconvTanh, gd_deconv.GDDeconvTanh)
    reg["deconv_sigmoid"] = (deconv.DeconvSigmoid, gd_deconv.GDDeconvSigmoid)
    reg["depooling"] = (depooling.Depooling, depooling.GDDepooling)
    reg["attention"] = (attention.MultiHeadAttention,
                        attention.GDMultiHeadAttention)
    from znicz_tpu import decoder
    from znicz_tpu.nn_units import GradientDescentAdamW

    for kind, cls in (("token_embedding", decoder.TokenEmbedding),
                      ("decoder_layer", decoder.DecoderLayer),
                      ("lm_head", decoder.LMHead)):
        reg[kind] = (cls, GradientDescentAdamW)
    try:
        from znicz_tpu import resizable_all2all

        reg["resizable_all2all"] = (resizable_all2all.ResizableAll2All,
                                    gd.GradientDescent)
    except ImportError:
        pass
    return reg


#: unit types whose train/eval behavior depends on the minibatch class
_MODE_SWITCHED = ("dropout", "stochastic_pooling", "stochastic_abs_pooling")


class StandardWorkflowBase(Workflow):
    """Holds the builder pieces; StandardWorkflow drives them in order."""

    def __init__(self, workflow=None, name=None, loader=None,
                 layers: List[dict] = (), loss_function: str = "softmax",
                 decision_config: Optional[dict] = None,
                 snapshotter_config: Optional[dict] = None,
                 lr_adjust_config: Optional[dict] = None,
                 image_saver_config: Optional[dict] = None,
                 plotters: bool = False, **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        assert loader is not None, "StandardWorkflow needs a loader instance"
        self.layers_config = list(layers)
        self.loss_function = loss_function
        self.decision_config = dict(decision_config or {})
        self.snapshotter_config = dict(snapshotter_config or {})
        #: e.g. {"policy": "exp", "gamma": 0.96} (see lr_adjust.POLICIES);
        #: the reference's StandardWorkflow wired lr_adjust into the chain
        #: the same way (SURVEY §2.2)
        self.lr_adjust_config = dict(lr_adjust_config or {})
        self.lr_adjust = None
        #: SURVEY §2.2 StandardWorkflow row also auto-links plotters and
        #: image_saver; both optional here.  image_saver_config (dict,
        #: e.g. {"limit": 32}) dumps misclassified samples per epoch;
        #: plotters=True wires the error curve + first-layer Weights2D +
        #: confusion MatrixPlotter at epoch boundaries — the fused fast
        #: path runs these too (its epoch hook).  image_saver consumes
        #: per-minibatch host data the fast path never pulls, so it is
        #: unit-engine-only.
        self.image_saver_config = image_saver_config
        self.want_plotters = bool(plotters)
        self.image_saver = None
        self.plotters = []
        self.loader = loader
        self.add_unit(loader)
        self.forwards = []
        self.gds = []

    # -- builder steps --------------------------------------------------------

    def link_repeater(self):
        self.repeater = Repeater(self, name="repeater")
        self.repeater.link_from(self.start_point)

    def link_loader(self):
        self.loader.link_from(self.repeater)

    def parse_forwards_from_config(self):
        reg = _registry()
        prev, prev_attr = self.loader, "minibatch_data"
        for i, layer in enumerate(self.layers_config):
            kind = layer["type"]
            if kind not in reg:
                raise ValueError(f"unknown layer type {kind!r} "
                                 f"(known: {sorted(reg)})")
            fwd_cls, _ = reg[kind]
            fwd = fwd_cls(self, name=f"fwd_{kind}_{i}",
                          **layer.get("->", {}))
            fwd.layer_index = i
            fwd.layer_kind = kind
            fwd.link_from(prev if i == 0 else self.forwards[-1])
            fwd.link_attrs(prev, ("input", prev_attr))
            if kind in _MODE_SWITCHED:
                fwd.link_attrs(self.loader, "minibatch_class")
            self.forwards.append(fwd)
            prev, prev_attr = fwd, "output"

    def link_evaluator(self):
        last = self.forwards[-1]
        if self.loss_function == "softmax":
            self.evaluator = EvaluatorSoftmax(self, name="evaluator")
            self.evaluator.link_attrs(self.loader,
                                      ("labels", "minibatch_labels"))
        elif self.loss_function == "mse":
            self.evaluator = EvaluatorMSE(self, name="evaluator")
            self.evaluator.link_attrs(self.loader,
                                      ("target", "minibatch_targets"))
        else:
            raise ValueError(f"unknown loss {self.loss_function!r}")
        self.evaluator.link_from(last)
        self.evaluator.link_attrs(last, "output")
        self.evaluator.link_attrs(self.loader,
                                  ("batch_size", "minibatch_size"))

    def link_decision(self):
        cls = DecisionGD if self.loss_function == "softmax" else DecisionMSE
        self.decision = cls(self, name="decision", **self.decision_config)
        self.decision.link_from(self.evaluator)
        self.decision.link_attrs(
            self.loader, "minibatch_class", "last_minibatch", "class_ended",
            "epoch_number", "class_lengths", "minibatch_size")
        self.decision.link_attrs(self.evaluator, ("minibatch_loss", "loss"))
        if self.loss_function == "softmax":
            self.decision.link_attrs(
                self.evaluator, ("minibatch_n_err", "n_err"),
                "confusion_matrix", "max_err_output_sum")

    def link_snapshotter(self):
        self.snapshotter = Snapshotter(self, name="snapshotter",
                                       **self.snapshotter_config)
        self.snapshotter.link_from(self.decision)
        self.snapshotter.link_attrs(self.decision, "epoch_number")
        self.snapshotter.improved = self.decision.improved
        self.snapshotter.gate_skip = ~self.decision.epoch_ended

    def create_gd_units(self):
        reg = _registry()
        err_src, err_attr = self.evaluator, "err_output"
        tail = self.snapshotter
        for i in reversed(range(len(self.forwards))):
            fwd = self.forwards[i]
            layer = self.layers_config[i]
            _, gd_cls = reg[fwd.layer_kind]
            if gd_cls is None:
                raise ValueError(
                    f"layer {fwd.layer_kind!r} has no backward unit and "
                    "cannot sit inside a GD chain")
            gd = gd_cls(self, name=f"gd_{fwd.layer_kind}_{i}", forward=fwd,
                        need_err_input=(i > 0),
                        **layer.get("<-", {}))
            gd.link_from(tail)
            gd.link_attrs(err_src, ("err_output", err_attr))
            gd.gate_skip = self.decision.gd_skip
            self.gds.append(gd)
            err_src, err_attr, tail = gd, "err_input", gd

    def link_lr_adjust(self):
        """Splice a LearningRateAdjust unit after the gd chain (one policy
        instance per gd so per-unit iteration state can't alias), gated
        like the gds.  No-op without ``lr_adjust_config``."""
        if not self.lr_adjust_config or not self.gds:
            return
        from znicz_tpu.lr_adjust import LearningRateAdjust, make_policy

        cfg = dict(self.lr_adjust_config)
        policy_name = cfg.pop("policy")
        self.lr_adjust = LearningRateAdjust(self, name="lr_adjust")
        for gd in self.gds:
            self.lr_adjust.add_gd(gd, make_policy(policy_name, **cfg))
        self.lr_adjust.link_from(self.gds[-1])
        self.lr_adjust.gate_skip = self.decision.gd_skip

    def link_observers(self):
        """Optional side units (SURVEY §2.2: "plotters/image_saver")."""
        if self.image_saver_config is not None and self.loss_function == \
                "softmax":
            from znicz_tpu.image_saver import ImageSaver

            sv = ImageSaver(self, name="image_saver",
                            **self.image_saver_config)
            sv.link_from(self.evaluator)
            sv.link_attrs(self.loader, ("input", "minibatch_data"),
                          ("labels", "minibatch_labels"),
                          ("batch_size", "minibatch_size"),
                          "epoch_number", "last_minibatch")
            sv.link_attrs(self.forwards[-1], "output")
            self.image_saver = sv
        if self.want_plotters:
            from znicz_tpu.plotting_units import (AccumulatingPlotter,
                                                  MatrixPlotter, Weights2D)

            dec = self.decision

            def valid_metric():
                # validation metrics when a VALID split exists, else the
                # TRAIN epoch metrics; key depends on the decision kind
                # (DecisionGD: err_pct, DecisionMSE: mse/loss)
                m = dec.epoch_metrics[1] or dec.epoch_metrics[2] or {}
                for key in ("err_pct", "mse", "loss"):
                    if key in m:
                        return float(m[key])
                return 0.0

            err = AccumulatingPlotter(
                self, name="plot_err",
                ylabel=("valid err %" if self.loss_function == "softmax"
                        else "valid loss"),
                fetch=valid_metric)
            plots = [err]
            first_weighted = next(
                (f for f in self.forwards if f.has_weights), None)
            if first_weighted is not None:
                plots.append(Weights2D(self, name="plot_weights",
                                       source=first_weighted.weights))
            if self.loss_function == "softmax":
                import numpy as _np

                def valid_confusion():
                    conf = (dec.epoch_metrics[1] or {}).get("confusion")
                    return _np.asarray(conf if conf is not None
                                       else [[0]])

                plots.append(MatrixPlotter(self, name="plot_confusion",
                                           fetch=valid_confusion))
            prev = self.snapshotter
            for p in plots:
                p.link_from(prev)
                p.gate_skip = ~self.decision.epoch_ended   # epoch ends only
                prev = p
            self.plotters = plots

    def link_loop_and_end(self):
        loop_tail = (self.lr_adjust or (self.gds[-1] if self.gds
                                        else self.decision))
        self.repeater.link_from(loop_tail)
        self.end_point.link_from(self.decision)
        if self.plotters:
            # the final epoch's plots must render before the run stops —
            # EndPoint waits for the plot chain too (gate-skipped units
            # still propagate control on ordinary laps).  That makes the
            # stop lap reach the repeater before EndPoint pops, so block
            # the repeater once training completed — the loader must not
            # advance past the end of training
            self.end_point.link_from(self.plotters[-1])
            self.repeater.gate_block = self.decision.complete
        self.end_point.gate_block = ~self.decision.complete


class StandardWorkflow(StandardWorkflowBase):
    """One-call builder: constructs the full training graph in the reference
    order.  Subclass and override individual ``link_*`` steps to customize
    (that was the reference's extension pattern too)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.link_repeater()
        self.link_loader()
        self.parse_forwards_from_config()
        self.link_evaluator()
        self.link_decision()
        self.link_snapshotter()
        self.create_gd_units()
        self.link_lr_adjust()
        self.link_observers()
        self.link_loop_and_end()
