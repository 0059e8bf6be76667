"""The snowman ChainVM (role of /root/reference/plugin/evm/vm.go).

Initialize wires config → databases → genesis/fork config → chain backend
→ mempools → atomic state (vm.go:315-549); buildBlock assembles through
the miner + atomic mempool (:991-1032); parseBlock/getBlock/SetPreference
serve the consensus engine (:1034-1096). Atomic txs flow through the
ConsensusCallbacks into block bodies (vm.go:696-851).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from .. import params, rlp
from ..consensus.dummy import ConsensusCallbacks, DummyEngine
from ..core.blockchain import BlockChain, CacheConfig
from ..core.genesis import Genesis
from ..core.txpool import TxPool, TxPoolConfig
from ..core.types import Block as EthBlock
from ..miner.worker import Worker
from ..state.database import Database
from ..trie.triedb import TrieDatabase
from .atomic_tx import (
    Tx,
    calculate_dynamic_fee,
    decode_tx,
    encode_atomic_txs,
    extract_atomic_txs,
)
from .block import BlockStatus, VMBlock
from .mempool import Mempool
from .shared_memory import Requests

AVAX_ASSET_ID = b"\x41" * 32  # test default; ctx overrides

# accepted-atomic-tx index (atomic_tx_repository.go role). "Atx" cannot
# collide with snapshot (b"a"/b"o"), header/body (b"h"/b"b"), code (b"c"),
# or 32-byte trie-node keys.
ATOMIC_TX_INDEX_PREFIX = b"Atx"


@dataclass
class VMConfig:
    """plugin/evm/config.go subset — the knobs the runtime honors now."""

    pruning: bool = True
    commit_interval: int = 4096
    mempool_size: int = 4096
    clock: Optional[object] = None
    # flat snapshot tree (config.go snapshot-cache; 0 disables). The VM
    # serves sync leaves from it when enabled (leafs_request fast path).
    snapshot_limit: int = 256
    # "auto"/"batched": drain large dirty sets to the device keccak from
    # Trie.hash (trie/trie.go:618-619 parallel-threshold analog); "off": CPU
    device_hasher: str = "auto"
    # device-resident account trie (CacheConfig.resident_account_trie):
    # per-block account hashing as one resident commit on the mirror.
    # "auto": ON when a TPU backend resolves (production default)
    resident_account_trie: "bool | str" = "auto"
    # watchdog (s) per resident device commit; expiry -> host takeover
    resident_commit_timeout: "float | None" = 180.0
    # resident mirror host preference ("auto": host commits whenever no
    # TPU backend resolves; True/False force)
    resident_prefer_host: "bool | str" = "auto"
    # native CPU hasher worker threads; 0 = auto
    cpu_threads: int = 0


@dataclass
class SnowContext:
    """snow.Context subset the VM needs (ids + shared memory)."""

    network_id: int = 1337
    chain_id: bytes = b"\x02" * 32          # this blockchain's avalanche ID
    x_chain_id: bytes = b"\x58" * 32
    avax_asset_id: bytes = AVAX_ASSET_ID
    shared_memory: object = None


class VMError(Exception):
    pass


class VM:
    def __init__(self):
        self.initialized = False

    # --- snowman ChainVM: Initialize (vm.go:315-549) ----------------------

    def initialize(
        self,
        ctx: SnowContext,
        diskdb,
        genesis: Genesis,
        config: VMConfig = None,
        to_engine=None,
        config_bytes: bytes = b"",
    ) -> None:
        self.ctx = ctx
        if config_bytes:
            # JSON blob from the node (vm.go:326-334) → runtime knobs; a
            # VMConfig passed beside it contributes only its clock
            from .config import parse_config

            full = parse_config(config_bytes)
            self.full_config = full
            config = VMConfig(
                clock=config.clock if config is not None else None,
                pruning=full.pruning_enabled,
                commit_interval=full.commit_interval,
                mempool_size=full.tx_pool_global_slots,
                device_hasher=full.device_hasher,
                resident_account_trie=full.resident_account_trie,
                # pass 0 through untouched: the mirror reads it as
                # "explicitly disabled" — collapsing it to None would
                # re-open the env-var override the operator turned off
                resident_commit_timeout=full.resident_commit_timeout,
                resident_prefer_host=full.resident_prefer_host,
                cpu_threads=full.cpu_threads,
            )
        else:
            from .config import Config as FullConfig

            self.full_config = FullConfig()
        self.config = config or VMConfig()
        self.chain_config = genesis.config
        self.network_id = ctx.network_id
        self.chain_id_bytes = ctx.chain_id
        self.avax_asset_id = ctx.avax_asset_id
        self.shared_memory = (
            ctx.shared_memory.new_shared_memory(ctx.chain_id)
            if hasattr(ctx.shared_memory, "new_shared_memory")
            else ctx.shared_memory
        )
        self.atomic_codec = None
        self.to_engine = to_engine  # callable: notify engine txs are ready

        # honor global observability knobs (vm.go:344-353 log config;
        # metrics.EnabledExpensive gate) — ONLY when the blob set them:
        # these are process-global, and a second VM in the same process
        # must not silently reset the first one's diagnostics
        explicit = getattr(self.full_config, "explicit_keys", set())
        if "log_level" in explicit:
            from .. import log as _log

            _log.set_level(self.full_config.log_level)
        if "metrics_expensive_enabled" in explicit:
            from .. import metrics as _metrics

            _metrics.enabled_expensive = (
                self.full_config.metrics_expensive_enabled)
        if "evm_fastloop" in explicit:
            from ..evm import interpreter as _interp

            _interp.FASTLOOP_DEFAULT = bool(self.full_config.evm_fastloop)
        if "spans_enabled" in explicit:
            from ..metrics import spans as _spans

            _spans.set_enabled(self.full_config.spans_enabled)
        if "span_ring_size" in explicit:
            from ..metrics import spans as _spans

            _spans.tracer.set_capacity(self.full_config.span_ring_size)
        if "tracing_enabled" in explicit:
            from ..metrics import tracectx as _tracectx

            _tracectx.set_enabled(self.full_config.tracing_enabled)
        if "trace_ring_size" in explicit:
            from ..metrics import tracectx as _tracectx

            _tracectx.ring.set_capacity(self.full_config.trace_ring_size)
        if "lock_slow_hold_budget" in explicit:
            from ..utils import racecheck as _racecheck

            _racecheck.set_slow_hold_budget(
                self.full_config.lock_slow_hold_budget)
        if "shard_telemetry_enabled" in explicit:
            from ..core import exec_shards as _exec_shards

            _exec_shards.set_telemetry_enabled(
                self.full_config.shard_telemetry_enabled)

        # node keystore (node/ keystore dir role; backs avax.importKey/
        # exportKey/import/export and the eth/personal signing RPC)
        ks_dir = getattr(self.full_config, "keystore_directory", "")
        if ks_dir:
            from ..accounts.keystore import KeyStore

            self.keystore = KeyStore(ks_dir)
        else:
            self.keystore = None
        # external (clef-style) signer daemon (accounts/external/
        # backend.go role): its accounts merge into eth_accounts, and
        # eth_signTransaction/sendTransaction for them route over IPC
        self.external_signer = None
        ext_path = getattr(self.full_config, "keystore_external_signer", "")
        if ext_path:
            from ..accounts.external import ExternalSigner

            self.external_signer = ExternalSigner(ext_path)

        clock = self.config.clock or (lambda: self._now())

        cb = ConsensusCallbacks(
            on_finalize_and_assemble=self._on_finalize_and_assemble,
            on_extra_state_change=self._on_extra_state_change,
        )
        self.engine = DummyEngine(cb)

        from ..ops.device import get_batch_keccak

        self.state_database = Database(TrieDatabase(
            diskdb, batch_keccak=get_batch_keccak(self.config.device_hasher)
        ))
        full = self.full_config
        self.blockchain = BlockChain(
            diskdb,
            CacheConfig(
                pruning=self.config.pruning,
                commit_interval=self.config.commit_interval,
                device_hasher=self.config.device_hasher,
                resident_account_trie=self.config.resident_account_trie,
                resident_commit_timeout=self.config.resident_commit_timeout,
                resident_prefer_host=self.config.resident_prefer_host,
                cpu_threads=self.config.cpu_threads,
                snapshot_limit=self.config.snapshot_limit,
                trie_dirty_limit=full.trie_dirty_cache * 1024 * 1024,
                accepted_cache_size=full.accepted_cache_size,
                flight_recorder_size=full.flight_recorder_size,
                device_call_timeout=full.device_call_timeout,
                device_max_retries=full.device_max_retries,
                device_probe_interval=full.device_probe_interval,
                device_promote_after=full.device_promote_after,
                resident_spot_check_interval=(
                    full.resident_spot_check_interval),
                resident_pipeline_depth=full.resident_pipeline_depth,
                insert_pipeline_depth=full.insert_pipeline_depth,
                resident_template_residency=(
                    full.resident_template_residency),
                resident_mesh_devices=full.resident_mesh_devices,
                tail_join_timeout=full.tail_join_timeout,
                db_verify_on_read=full.db_verify_on_read,
                db_retry_budget=full.db_retry_budget,
                state_backend=full.state_backend,
                shadow_check_interval=full.shadow_check_interval,
                evm_parallel_workers=full.evm_parallel_workers,
                evm_exec_shards=full.evm_exec_shards,
                insert_slo_budget=full.chain_insert_slo_budget,
            ),
            self.chain_config,
            genesis,
            self.engine,
            state_database=self.state_database,
        )
        self.txpool = TxPool(
            TxPoolConfig(
                price_limit=full.tx_pool_price_limit,
                price_bump=full.tx_pool_price_bump,
                account_slots=full.tx_pool_account_slots,
                global_slots=full.tx_pool_global_slots,
                account_queue=full.tx_pool_account_queue,
                global_queue=full.tx_pool_global_queue,
            ),
            self.chain_config, self.blockchain,
        )
        self.miner = Worker(
            self.chain_config, self.engine, self.blockchain,
            tx_pool=self.txpool, clock=clock,
        )

        # fork-scheduled gas-price floors (vm.go handleGasPriceUpdates).
        # Wall clock on purpose: fork timestamps are wall times and the
        # reference schedules with time.Until — the VM's block-timestamp
        # clock override must not skew the schedule.
        from .plumbing import GasPriceUpdater

        self.gas_price_updater = GasPriceUpdater(
            self.txpool, self.chain_config)
        self.gas_price_updater.start()

        def price(tx: Tx) -> int:
            gas = max(tx.gas_used(self.current_rules().is_apricot_phase5), 1)
            return tx.burned(self.avax_asset_id) // gas

        def fits_atomic_gas(tx: Tx) -> bool:
            rules = self.current_rules()
            if not rules.is_apricot_phase5:
                return True
            return tx.gas_used(True) <= params.ATOMIC_GAS_LIMIT

        self.mempool = Mempool(
            self.config.mempool_size, fee_fn=price, max_tx_gas=fits_atomic_gas
        )

        # atomic ops index with interval commits (atomic_trie.go)
        from .atomic_trie import AtomicTrie

        self.atomic_trie = AtomicTrie(
            diskdb, self.config.commit_interval,
            batch_keccak=get_batch_keccak(self.config.device_hasher),
        )

        self._verified_blocks: Dict[bytes, VMBlock] = {}
        self._accepted_atomic_ops: List = []

        # per-verified-block pending atomic state + tx repository
        # (atomic_backend.go / atomic_tx_repository.go)
        from .atomic_backend import AtomicBackend

        self.atomic_backend = AtomicBackend(self)
        genesis_vmb = VMBlock(self, self.blockchain.genesis_block)
        genesis_vmb.status = BlockStatus.ACCEPTED
        self.last_accepted_vm_block = genesis_vmb
        self.preferred_block: VMBlock = genesis_vmb
        self._building_txs: List[Tx] = []
        self.lock = threading.RLock()
        self.initialized = True

        # notify the engine when txs arrive (block_builder.go signal)
        # build throttling (block_builder.go:55-129): one PendingTxs
        # notification per outstanding build, retry-timer recovery
        from .block_builder import BlockBuilder

        self.block_builder = BlockBuilder(self)
        self.txpool.subscribe_new_txs(lambda txs: self._signal_txs_ready())

        # archival trie-gap healing behind the config knob (vm.go startup
        # order; core/blockchain.go:1899 populateMissingTries)
        if self.full_config.populate_missing_tries is not None:
            self.blockchain.populate_missing_tries(
                self.full_config.populate_missing_tries,
                self.full_config.populate_missing_tries_parallelism,
            )

        # inbound sync server (vm.go:547 initializeStateSyncServer): leaf/
        # block/code requests served off this chain, snapshot fast path
        # engaged automatically when the chain runs one
        from ..sync.handlers import SyncHandler

        self.sync_handler = SyncHandler(
            self.blockchain, self.state_database.triedb, diskdb
        )

        # continuous profiler (vm.go:1642, config.go:89-91)
        self.continuous_profiler = None
        if self.full_config.continuous_profiler_dir:
            from .api import ContinuousProfiler

            self.continuous_profiler = ContinuousProfiler(
                self.full_config.continuous_profiler_dir,
                freq=self.full_config.continuous_profiler_frequency,
                max_files=self.full_config.continuous_profiler_max_files,
            ).start()

        # in-process sampling profiler (metrics/profiler.py): daemon
        # thread, refcounted process-global singleton — a second VM (or
        # the chaos conductor) takes a reference on the same sampler and
        # our shutdown only drops ours
        self.sampling_profiler = None
        if self.full_config.profiler_hz > 0:
            from ..metrics import profiler as _profiler

            self.sampling_profiler = _profiler.start_profiler(
                self.full_config.profiler_hz,
                ring_size=self.full_config.profiler_ring_size)

        # stdlib /metrics + /healthz endpoint (metrics/http.py), reusing
        # the health_check verdict the RPC health namespace serves
        self.metrics_http = None
        if self.full_config.metrics_http_enabled:
            from ..metrics.http import MetricsHTTPServer
            from .api import health_check

            self.metrics_http = MetricsHTTPServer(
                health_fn=lambda: health_check(self))
            self.metrics_http.start(
                host=self.full_config.metrics_http_host,
                port=self.full_config.metrics_http_port,
            )

    @staticmethod
    def _now() -> int:
        import time

        return int(time.time())

    def current_rules(self):
        head = self.blockchain.current_block
        return self.chain_config.rules(head.number + 1, head.time)

    def _signal_txs_ready(self) -> None:
        self.block_builder.signal_txs_ready()

    # --- consensus callbacks (vm.go:696-851) ------------------------------

    def _on_finalize_and_assemble(self, header, state, txs):  # guarded-by: lock
        """Pull atomic txs from the mempool into the block being built."""
        rules = self.chain_config.rules(header.number, header.time)
        batch = rules.is_apricot_phase5
        picked: List[Tx] = []
        contribution = 0
        ext_gas_used = 0
        while True:
            tx = self.mempool.next_tx()
            if tx is None:
                break
            inner_snap = state.snapshot()
            try:
                tx.semantic_verify(self, header.base_fee)
                tx.evm_state_transfer(self, state)
            except Exception:
                from ..metrics import count_drop

                count_drop("vm/build/atomic_tx_invalid")
                state.revert_to_snapshot(inner_snap)
                self.mempool.remove_tx(tx)
                continue
            if rules.is_apricot_phase4:
                try:
                    contrib, gas = tx.block_fee_contribution(
                        rules.is_apricot_phase5, self.avax_asset_id, header.base_fee
                    )
                    contribution += contrib
                    ext_gas_used += gas
                except Exception:
                    from ..metrics import count_drop

                    count_drop("vm/build/atomic_tx_fee_error")
                    state.revert_to_snapshot(inner_snap)
                    self.mempool.remove_tx(tx)
                    continue
            if batch and ext_gas_used > params.ATOMIC_GAS_LIMIT:
                # this tx overflows the AP5 atomic gas budget: undo its
                # state changes, requeue it, and build with what we have
                state.revert_to_snapshot(inner_snap)
                if rules.is_apricot_phase4:
                    # undo the contribution accounting added above
                    contrib, gas = tx.block_fee_contribution(
                        rules.is_apricot_phase5, self.avax_asset_id, header.base_fee
                    )
                    contribution -= contrib
                    ext_gas_used -= gas
                self.mempool.cancel_current_tx(tx.id())
                break
            picked.append(tx)
            if not batch:
                break
        self._building_txs = picked
        ext_data = encode_atomic_txs(picked, batch)
        return ext_data, contribution, ext_gas_used

    def _on_extra_state_change(self, block, state):
        """Verify-side: apply the block's atomic txs to the state."""
        rules = self.chain_config.rules(block.number, block.time)
        txs = extract_atomic_txs(
            block.ext_data, rules.is_apricot_phase5, self.atomic_codec
        )
        contribution = 0
        ext_gas_used = 0
        for tx in txs:
            tx.evm_state_transfer(self, state)
            if rules.is_apricot_phase4:
                contrib, gas = tx.block_fee_contribution(
                    rules.is_apricot_phase5, self.avax_asset_id, block.base_fee
                )
                contribution += contrib
                ext_gas_used += gas
        return contribution, ext_gas_used

    # --- snowman interface -------------------------------------------------

    def build_block(self) -> VMBlock:
        """buildBlock (vm.go:991-1032)."""
        try:
            from ..metrics.spans import span

            with span("vm/buildBlock",
                      number=self.blockchain.current_block.number + 1):
                return self._build_block_inner()
        finally:
            # the engine consumed the PendingTxs notification by calling
            # us — success or not, reopen the gate + arm the retry timer
            # (block_builder.go handleGenerateBlock)
            self.block_builder.handle_generate_block()

    def _build_block_inner(self) -> VMBlock:
        from ..metrics import default_registry
        from ..metrics.flight import BuildRecorder

        recorder = self.blockchain.flight_recorder
        with self.lock:
            self._building_txs = []
            # the block's flight record gets this build's own section:
            # its state commit runs (and compiles) in the miner's preview
            build = BuildRecorder(default_registry)
            try:
                with build.phase("miner_execute", inner=(
                        "preview_commit", "resident/phase/preview")):
                    eth_block = self.miner.commit_new_work()
                if not eth_block.transactions and not self._building_txs:
                    raise VMError("block contains no transactions")
                vmb = VMBlock(self, eth_block)
                # verify without writes: re-executes like a peer would
                vmb.syntactic_verify()
                with build.phase("preverify"):
                    self.blockchain.insert_block_manual(eth_block,
                                                        writes=False)
            except Exception as e:
                recorder.note_event("vm/build_failed",
                                    error=type(e).__name__,
                                    build=build.section())
                # requeue any atomic txs popped into 'issued' during the
                # failed build (vm.go buildBlock error path CancelCurrentTxs)
                for tx in list(self.mempool.issued.values()):
                    self.mempool.cancel_current_tx(tx.id())
                raise
            recorder.note_build(eth_block.hash(), build.section())
            self.mempool.issue_current_txs()
            return vmb

    def parse_block(self, blob: bytes) -> VMBlock:
        eth_block = EthBlock.decode(blob)
        existing = self._verified_blocks.get(eth_block.hash())
        if existing is not None:
            return existing
        return VMBlock(self, eth_block)

    def get_block(self, block_id: bytes) -> Optional[VMBlock]:
        vmb = self._verified_blocks.get(block_id)
        if vmb is not None:
            return vmb
        eth_block = self.blockchain.get_block(block_id)
        if eth_block is None:
            return None
        vmb = VMBlock(self, eth_block)
        if self.blockchain.get_canonical_hash(eth_block.number) == block_id and (
            eth_block.number <= self.last_accepted_vm_block.height()
        ):
            vmb.status = BlockStatus.ACCEPTED
        return vmb

    def set_preference(self, block_id: bytes) -> None:
        """SetPreference (vm.go:1076)."""
        vmb = self.get_block(block_id)
        if vmb is None:
            raise VMError("cannot set preference to unknown block")
        self.preferred_block = vmb
        self.blockchain.set_preference(vmb.eth_block)

    def last_accepted(self) -> VMBlock:
        return self.last_accepted_vm_block

    def shutdown(self) -> None:
        if self.initialized:
            self.block_builder.shutdown()
            self.gas_price_updater.stop()
            if self.continuous_profiler is not None:
                self.continuous_profiler.stop()
            if self.sampling_profiler is not None:
                from ..metrics import profiler as _profiler

                # drops only THIS VM's reference — other holders of the
                # process sampler keep sampling
                _profiler.stop_profiler()
                self.sampling_profiler = None
            if self.metrics_http is not None:
                self.metrics_http.stop()
            # graceful RPC drain first: in-flight reads finish (bounded
            # by rpc-drain-timeout) before the chain under them stops
            rpc_server = getattr(self, "rpc_server", None)
            if rpc_server is not None:
                rpc_server.stop()
                self.rpc_server = None
            self.blockchain.stop()

    # --- VMBlock support ---------------------------------------------------

    def add_verified_block(self, vmb: VMBlock) -> None:
        self._verified_blocks[vmb.id()] = vmb

    def forget_verified_block(self, block_id: bytes) -> None:
        self._verified_blocks.pop(block_id, None)

    def set_last_accepted(self, vmb: VMBlock) -> None:
        self.last_accepted_vm_block = vmb

    def atomic_backend_apply(self, vmb: VMBlock, tx: Tx) -> None:
        """Back-compat single-tx apply; the accept path now drains whole
        blocks through AtomicBackend.accept (atomic_backend.py)."""
        chain, requests = tx.atomic_ops()
        batch = self.blockchain.diskdb.new_batch()
        batch.put(
            ATOMIC_TX_INDEX_PREFIX + tx.id(),
            vmb.height().to_bytes(8, "big") + tx.encode(),
        )
        self.shared_memory.apply({chain: requests}, batch=batch)
        self.mempool.remove_tx(tx)
        self.atomic_trie.index(vmb.height(), {chain: requests})

    # --- atomic tx issuance (vm.go:1297-1417) -----------------------------

    # --- cross-chain eth_call capability (peer/network.go:199-301 +
    # message/eth_call_request.go): another chain's VM evaluates a
    # read-only call against OUR latest accepted state ------------------

    def handle_cross_chain_request(self, blob: bytes) -> bytes:
        """Typed cross-chain dispatcher: register with
        Network.register_cross_chain_handler(vm.chain_id_bytes, ...)."""
        import json as _json

        from ..sync.messages import (EthCallRequest, EthCallResponse,
                                     decode_message)

        msg = decode_message(blob)
        if not isinstance(msg, EthCallRequest):
            raise VMError(f"unsupported cross-chain request {type(msg)}")
        backend = getattr(self, "eth_backend", None)
        if backend is None:
            from ..eth.backend import EthBackend

            backend = EthBackend(self.blockchain, self.txpool)
            self.eth_backend = backend
        try:
            call_obj = _json.loads(msg.request_args.decode())
            result, _, _ = backend.do_call(call_obj, "latest")
        except Exception as e:  # noqa: BLE001 — errors travel in-band
            return EthCallResponse(result=b"", error=str(e).encode()).encode()
        if result.err is not None:
            return EthCallResponse(result=result.return_data,
                                   error=str(result.err).encode()).encode()
        return EthCallResponse(result=result.return_data).encode()

    def cross_chain_eth_call(self, network, chain_id: bytes,
                             call_obj: dict, deadline: float = 10.0):
        """Client side: eth_call on [chain_id]'s VM over the cross-chain
        transport. Returns the raw return data; raises VMError with the
        remote error string on failure."""
        import json as _json

        from ..sync.messages import EthCallRequest, decode_message

        req = EthCallRequest(
            request_args=_json.dumps(call_obj).encode()).encode()
        resp = decode_message(
            network.send_cross_chain_request(chain_id, req, deadline))
        if resp.error:
            raise VMError(
                f"cross-chain eth_call failed: {resp.error.decode()}")
        return resp.result

    def issue_atomic_tx(self, tx: Tx) -> None:
        tx.semantic_verify(self, self._next_base_fee())
        self.mempool.add(tx)
        self._signal_txs_ready()

    def _next_base_fee(self) -> Optional[int]:
        head = self.blockchain.current_block.header
        if not self.chain_config.is_apricot_phase3(head.time):
            return None
        from ..consensus.dummy import estimate_next_base_fee

        _, fee = estimate_next_base_fee(self.chain_config, head, head.time)
        return fee

    def issue_tx(self, tx) -> None:
        """eth tx entry (API/gossip)."""
        self.txpool.add_local(tx)
