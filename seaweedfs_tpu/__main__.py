from .util.device import setup_compile_cache

setup_compile_cache()  # before anything imports jax

from .command.cli import main  # noqa: E402

raise SystemExit(main())
