"""Package build (reference analogue: DeepSpeed setup.py — minus the CUDA
op pre-build matrix; the only native component, the aio engine, JIT-compiles
on first use via g++ and needs no build-time step)."""
from setuptools import find_packages, setup

setup(
    name="deepspeed_tpu",
    version="0.1.0",
    description="TPU-native large-scale training & inference framework "
                "(DeepSpeed capabilities on JAX/XLA/Pallas)",
    packages=find_packages(include=["deepspeed_tpu", "deepspeed_tpu.*"]),
    package_data={"deepspeed_tpu": ["csrc/*.cpp"]},
    python_requires=">=3.10",
    install_requires=[
        "jax>=0.9",
        "optax",
        "orbax-checkpoint",
        "pydantic>=2",
        "numpy",
    ],
    extras_require={
        "hf": ["transformers", "torch"],
        "dev": ["pytest", "chex"],
    },
    scripts=["bin/dstpu", "bin/ds_report", "bin/dstpu-telemetry",
             "bin/dstpu-check", "bin/dstpu-serve", "bin/dstpu-router",
             "bin/dstpu-trace", "bin/dstpu-fleet", "bin/dstpu-replay",
             "bin/dstpu-mem"],
)
