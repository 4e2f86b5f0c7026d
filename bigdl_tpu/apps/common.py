"""Shared CLI plumbing for the app mains (reference ``models/*/Utils.scala``
option parsers — scopt ``trainParser``/``testParser`` — and the optimizer
wiring repeated in every ``Train.scala``)."""

from __future__ import annotations

import argparse
import logging
from typing import Callable, Optional

from bigdl_tpu.optim import (Optimizer, SGD, Top1Accuracy, Top5Accuracy,
                             Loss, Trigger)
from bigdl_tpu.utils.logger_filter import redirect_logs


def train_parser(prog: str, default_batch: int = 128,
                 default_epochs: int = 5,
                 default_lr: float = 0.01) -> argparse.ArgumentParser:
    """Reference train option set (``models/lenet/Utils.scala:1-80``)."""
    p = argparse.ArgumentParser(prog=prog)
    p.add_argument("-f", "--folder", default=None,
                   help="dataset location (synthetic data when omitted)")
    p.add_argument("-b", "--batchSize", type=int, default=default_batch)
    p.add_argument("-e", "--maxEpoch", type=int, default=default_epochs)
    p.add_argument("-r", "--learningRate", type=float, default=default_lr)
    p.add_argument("--learningRateDecay", type=float, default=0.0)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weightDecay", type=float, default=0.0)
    p.add_argument("--model", default=None, help="model snapshot to resume")
    p.add_argument("--state", default=None, help="state snapshot to resume")
    p.add_argument("--checkpoint", default=None,
                   help="where to write model/state snapshots")
    p.add_argument("--overWriteCheckpoint", action="store_true")
    p.add_argument("--summary", default=None,
                   help="TensorBoard log dir (TrainSummary/ValidationSummary)")
    p.add_argument("--appName", default=prog)
    p.add_argument("--synthetic-size", type=int, default=2048,
                   help="records of synthetic data when no -f")
    p.add_argument("--gradientClipL2Norm", type=float, default=0.0,
                   help="clip gradients to this global L2 norm (0 = off; "
                   "reference setGradientClippingByl2Norm)")
    p.add_argument("--gradientClipConstant", type=float, nargs=2,
                   default=None, metavar=("MIN", "MAX"),
                   help="clamp every gradient element into [MIN, MAX] "
                   "(reference setConstantGradientClipping)")
    p.add_argument("--autoResume", action="store_true",
                   help="continue from the newest COMPLETE snapshot under "
                   "--checkpoint (partial writes rejected; "
                   "docs/RESILIENCE.md) — the relaunch half of preemption "
                   "survival")
    p.add_argument("--preemptSnapshot", action="store_true",
                   help="install SIGTERM hooks: a preemption notice "
                   "triggers one final end-of-step snapshot + RESUME "
                   "marker under --checkpoint, then exits "
                   "(bigdl_tpu.resilience)")
    return p


def test_parser(prog: str, default_batch: int = 128) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog=prog)
    p.add_argument("-f", "--folder", default=None)
    p.add_argument("--model", required=True, help="trained model snapshot")
    p.add_argument("-b", "--batchSize", type=int, default=default_batch)
    p.add_argument("--synthetic-size", type=int, default=2048)
    return p


def build_optimizer(model, train_set, criterion, args,
                    validation_set=None,
                    methods=None,
                    optim_method=None,
                    topology=None) -> Optimizer:
    """The per-model ``Train.scala`` body: optimizer + schedules + triggers
    + checkpoint + summaries, from parsed args. ``optim_method`` overrides
    the default SGD (e.g. textclassifier uses Adagrad, reference
    ``example/textclassification/TextClassifier.scala:241``); ``topology``
    a non-default ``MeshTopology`` (tensor/expert axes)."""
    redirect_logs()
    kwargs = {"topology": topology} if topology is not None else {}
    opt = Optimizer(model, train_set, criterion, **kwargs)
    opt.set_optim_method(optim_method or SGD(
        learningrate=args.learningRate,
        learningrate_decay=args.learningRateDecay,
        momentum=args.momentum,
        weightdecay=args.weightDecay))
    opt.set_end_when(Trigger.max_epoch(args.maxEpoch))
    if getattr(args, "gradientClipL2Norm", 0.0):
        opt.set_gradient_clipping_by_l2_norm(args.gradientClipL2Norm)
    if getattr(args, "gradientClipConstant", None):
        opt.set_constant_gradient_clipping(*args.gradientClipConstant)
    if args.model and args.state:
        opt.resume(args.model, args.state)
    if args.checkpoint:
        opt.set_checkpoint(args.checkpoint, Trigger.every_epoch())
        if args.overWriteCheckpoint:
            opt.overwrite_checkpoint()
    if getattr(args, "autoResume", False):
        opt.auto_resume()
    if getattr(args, "preemptSnapshot", False):
        opt.set_preemption_handler()
    if validation_set is not None:
        opt.set_validation(Trigger.every_epoch(), validation_set,
                           methods or [Top1Accuracy(), Top5Accuracy(), Loss()])
    if args.summary:
        from bigdl_tpu.visualization import TrainSummary, ValidationSummary
        opt.set_train_summary(TrainSummary(args.summary, args.appName))
        opt.set_validation_summary(
            ValidationSummary(args.summary, args.appName))
    return opt


def run_test(model_path: str, test_set, methods) -> None:
    """The per-model ``Test.scala`` body."""
    redirect_logs()
    from bigdl_tpu.utils import file_io
    from bigdl_tpu.nn.module import Module
    snap = file_io.load(model_path)
    if isinstance(snap, dict) and "params" in snap:
        raise SystemExit(
            "got a checkpoint dict; pass it through the owning model: "
            "use train --model/--state to resume, or save the module itself")
    model: Module = snap
    results = model.evaluate(test_set, methods)
    for result, method in results:
        logging.getLogger("bigdl_tpu.optim").info(
            "%s is %s", method.name, result)
        print(f"{method.name}: {result}")
