"""Child process for the out-of-process VM boundary test: build a chain
in THIS process and serve its snowman interface on the unix socket from
argv[1] (the role plugin/main.go:33 plays for the reference — the VM
binary the engine spawns).

Run directly: python tests/plugin_child.py /tmp/vm.sock [n_blocks]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# pin jax to CPU before anything can touch a device backend: the parent
# test process may hold the accelerator, and a child that reaches for
# it would fail or hang
try:
    import jax

    jax.config.update("jax_platforms", "cpu")
except Exception:  # noqa: BLE001 — fine if jax never loads
    pass


def main() -> None:
    sock_path = sys.argv[1]
    n_blocks = int(sys.argv[2]) if len(sys.argv) > 2 else 8

    from test_sync import build_server_vm

    from coreth_tpu.plugin import serve

    vm, _mem = build_server_vm(n_blocks=n_blocks)
    serve(vm, sock_path)


if __name__ == "__main__":
    main()
