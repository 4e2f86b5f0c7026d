"""Runnable training/eval entry points (reference §2.7: per-model ``Train``/
``Test`` mains with scopt CLIs, e.g. ``models/lenet/Train.scala:31``, plus the
synthetic-throughput harnesses ``models/utils/DistriOptimizerPerf.scala:32`` /
``LocalOptimizerPerf.scala``).

Usage mirrors ``spark-submit --class ...lenet.Train``:

    python -m bigdl_tpu.apps.lenet train -b 128 -e 5 [-f /path/to/mnist]
    python -m bigdl_tpu.apps.lenet test  --model ckpt_dir/model
    python -m bigdl_tpu.apps.vgg   train -b 128 [-f /path/to/cifar10]
    python -m bigdl_tpu.apps.perf  --model inception_v1 -b 128 -i 20

Every app runs on synthetic data when no ``-f`` folder is given (the
reference's Perf mains use constant|random synthetic input the same way), so
each path is drivable without datasets.
"""
