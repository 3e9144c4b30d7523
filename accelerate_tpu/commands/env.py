"""`accelerate-tpu env` — print the environment (parity: reference commands/env.py:47)."""

import argparse
import os
import platform


def register_subcommand(subparsers):
    parser = subparsers.add_parser("env", help="Print environment information")
    parser.add_argument("--config_file", default=None, help="Config file to inspect")
    parser.set_defaults(func=env_command)
    return parser


def env_command(args):
    import jax

    import accelerate_tpu

    info = {
        "`accelerate_tpu` version": accelerate_tpu.__version__,
        "Platform": platform.platform(),
        "Python version": platform.python_version(),
        "JAX version": jax.__version__,
        "JAX backend": jax.default_backend(),
        "Device count (global/local)": f"{jax.device_count()}/{jax.local_device_count()}",
        "Device kind": jax.devices()[0].device_kind,
        "Process count": jax.process_count(),
    }
    import flax
    import optax

    info["Flax version"] = flax.__version__
    info["Optax version"] = optax.__version__
    accelerate_env = {k: v for k, v in os.environ.items() if k.startswith("ACCELERATE_TPU_")}
    print("\nCopy-and-paste the text below in your GitHub issue\n")
    print("\n".join([f"- {prop}: {val}" for prop, val in info.items()]))
    if accelerate_env:
        print("- Environment config:")
        print("\n".join([f"  - {k}={v}" for k, v in sorted(accelerate_env.items())]))
    config_file = args.config_file or default_config_file()
    if os.path.isfile(config_file):
        with open(config_file) as f:
            print(f"- Config file ({config_file}):\n" + "".join(f"  {line}" for line in f))
    return info


def default_config_file() -> str:
    cache_dir = os.environ.get(
        "ACCELERATE_TPU_CONFIG_HOME", os.path.join(os.path.expanduser("~"), ".cache", "accelerate_tpu")
    )
    return os.path.join(cache_dir, "default_config.yaml")
