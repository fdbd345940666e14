"""The `loader-1m` configuration's server: the normal daemon with a Loader
attached, as an embedder of upstream's sets `Config.Loader`
(store.go:69-78; Load before serving, gubernator.go:138-148; Save after
the drain, :151-178).

    python -m benchmarks.loader_daemon

Upstream has no environment variable for a Loader and neither has this
program, so the configuration's `command` names this launcher instead of
`gubernator_tpu.cmd.daemon`: the same configuration from the same
environment, a Loader over two checkpoint files (benchmarks/snapshot.py
has the format) and the program's own `serve`. `BENCH_SNAPSHOT_IN` is
read at start, `BENCH_SNAPSHOT_OUT` written at shutdown; either may be
unset. Upstream's exported `MockLoader` (store.go:114-150) is a slice in
memory; a file is the floor of what a checkpoint costs.
"""

import os

from benchmarks import snapshot
from gubernator_tpu.cmd.daemon import serve
from gubernator_tpu.service.envconfig import setup_daemon_config
from gubernator_tpu.store.store import ItemSnapshot


class FileLoader:
    """`Loader{load, save}` over snapshot files. A token row's
    `created_at` is the program's `stamp`; rows go in and out as they are."""

    def __init__(self, path_in, path_out):
        self.path_in, self.path_out = path_in, path_out

    def load(self):
        if not self.path_in:
            return
        keys, cols = snapshot.read(self.path_in)
        columns = [cols[f].tolist() for f in snapshot.FIELDS]
        for key, algo, status, limit, duration, remaining, created, expire in zip(
                keys, *columns):
            yield ItemSnapshot(
                key=key, algorithm=algo, status=status, limit=limit,
                duration=duration, remaining=remaining, stamp=created,
                expire_at=expire)

    def save(self, items) -> None:
        if not self.path_out:
            return
        items = list(items)
        snapshot.write(self.path_out, [it.key for it in items], {
            f: [getattr(it, "stamp" if f == "created_at" else f) for it in items]
            for f in snapshot.FIELDS})


def main() -> None:
    conf = setup_daemon_config(None)
    conf.loader = FileLoader(os.environ.get("BENCH_SNAPSHOT_IN"),
                             os.environ.get("BENCH_SNAPSHOT_OUT"))
    serve(conf)


if __name__ == "__main__":
    main()
