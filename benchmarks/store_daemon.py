"""The `store-1m` configuration's server: the normal daemon with a Store
attached, as an embedder of upstream's sets `Config.Store` (store.go:49-65).

    python -m benchmarks.store_daemon

Upstream has no environment variable for a Store and neither has this
program, so the configuration's `command` names this launcher instead of
`gubernator_tpu.cmd.daemon`: the same configuration from the same
environment, the program's own `MemoryStore` (upstream's `MockStore`,
store.go:80), and the program's own `serve`. On a tree without `serve`
the import fails and the process exits non-zero at once.
"""

from gubernator_tpu.cmd.daemon import serve
from gubernator_tpu.service.envconfig import setup_daemon_config
from gubernator_tpu.store import MemoryStore


def main() -> None:
    conf = setup_daemon_config(None)
    conf.store = MemoryStore()
    serve(conf)


if __name__ == "__main__":
    main()
