"""Typed errors mirroring the reference's `CoconutErrorKind` (errors.rs:5-24),
with the SURVEY.md §5 mandate applied: no asserts in library code — hot-path
`assert!`/`unwrap` in the reference (signature.rs:133-134,289-290,449,477)
become raised, typed exceptions here.

WIRE CONTRACT (PR 13, coconut_tpu/net): every error class carries a stable
machine-readable `code` (a class attribute, overridable per instance by the
wire decoder) that maps 1:1 onto the gateway's error envelopes, and every
`ServiceRetryableError` carries a `retry_after_s` that is ALWAYS a finite
float >= 0 — constructors normalize None/negative/non-finite hints to 0.0
so neither local callers nor the wire codec ever defend against None."""

import math
import re


def _finite_retry_after(value):
    """Clamp a retry-after hint to a finite float >= 0 (0.0 = "no
    estimate, retry at will") — the wire-format invariant every
    ServiceRetryableError upholds."""
    if value is None:
        return 0.0
    try:
        value = float(value)
    except (TypeError, ValueError):
        return 0.0
    if not math.isfinite(value) or value < 0.0:
        return 0.0
    return value


class CoconutError(Exception):
    """Base class for all framework errors (reference: errors.rs:26-56).

    `code` is the stable machine-readable identifier the fleet gateway
    (coconut_tpu/net/wire.py) puts in error envelopes; subclasses override
    it, and the wire decoder may stamp a more specific instance-level code
    when reconstructing a remote error."""

    code = "error"


class UnsupportedNoOfMessages(CoconutError):
    """Verkey valid for `expected` messages but given `given` (errors.rs:7-11).

    Raised on RPC-reachable paths (signature.py / ps.py / pok_sig.py run
    server-side under the engine's mint and show-verify handlers), so it
    carries a stable wire code — without one it would cross the wire as a
    GeneralError and clients could no longer distinguish "wrong message
    count" (a permanent caller bug) from a generic failure."""

    code = "unsupported_messages"

    # class-level defaults: error_from_wire rebuilds non-retryable errors
    # via cls.__new__ + CoconutError.__init__, which never runs this
    # subclass __init__ — attribute reads must still succeed
    expected = None
    given = None

    def __init__(self, expected, given):
        super().__init__(
            "Verkey valid for %d messages but given %d messages" % (expected, given)
        )
        self.expected = expected
        self.given = given

    def _restore_wire_fields(self, message):
        # the message format above is part of the wire contract: the
        # structured counts survive the round trip
        m = re.search(r"valid for (\d+) messages but given (\d+)", message)
        if m is not None:
            self.expected = int(m.group(1))
            self.given = int(m.group(2))


class UnequalNoOfBasesExponents(CoconutError):
    """Same number of bases and exponents required (errors.rs:13-17).

    Wire-coded for the same reason as UnsupportedNoOfMessages: it is
    raised under the engine's show-verify handler (pok_vc.py /
    signature.py) on malformed proofs."""

    code = "unequal_bases_exponents"

    bases = None
    exponents = None

    def __init__(self, bases, exponents):
        super().__init__(
            "Same no of bases and exponents required. %d bases and %d exponents"
            % (bases, exponents)
        )
        self.bases = bases
        self.exponents = exponents

    def _restore_wire_fields(self, message):
        m = re.search(r"(\d+) bases and (\d+) exponents", message)
        if m is not None:
            self.bases = int(m.group(1))
            self.exponents = int(m.group(2))


class PSError(CoconutError):
    """Error raised by the PS-signature layer (errors.rs:19-20; ps_sig::errors).

    Wire-coded: ps.py's checks run under the engine's mint/show handlers,
    and a PS-layer refusal must stay distinguishable from a GeneralError
    across the gateway."""

    code = "ps_error"


class DeserializationError(CoconutError):
    """Malformed or non-canonical byte encoding (rebuild addition: the
    reference had no wire validation — SURVEY.md §4 'gaps to improve')."""

    code = "bad_request"


class GeneralError(CoconutError):
    """Catch-all with a message (errors.rs:22-23)."""

    code = "general"


class TransientBackendError(CoconutError):
    """A backend dispatch or readback failure that is expected to succeed
    on re-attempt (device preemption, host RPC hiccup, transient transfer
    failure). The stream supervision layer (stream.verify_stream +
    retry.RetryPolicy) retries these with bounded backoff and then falls
    back to a designated backend; any other exception class is treated as
    permanent and propagates immediately."""

    code = "transient"


class ServiceRetryableError(CoconutError):
    """Base for every LOUD-but-retriable refusal an online service emits
    (overload rejection, brownout shedding, quorum loss). The unified
    contract (coconut_tpu/engine): every subclass carries `program` — the
    engine program (verify / mint / prepare / show_prove / show_verify)
    that refused, or None for single-program legacy call sites — and
    `retry_after_s`, the service's hint for when capacity should be back:
    ALWAYS a finite float >= 0 (0.0 = no estimate; None / negative /
    non-finite hints are normalized at construction). Clients branch on
    this ONE type to implement backoff-and-resubmit without enumerating
    refusal kinds; `code` names the refusal kind machine-readably and is
    what the gateway's wire error envelopes carry."""

    code = "retryable"

    def __init__(self, message, program=None, retry_after_s=None):
        super().__init__(message)
        self.program = program
        self.retry_after_s = _finite_retry_after(retry_after_s)

    @classmethod
    def from_wire(cls, message, program=None, retry_after_s=0.0):
        """Reconstruct a retriable refusal from a decoded wire envelope.
        Bypasses the subclass constructor (an envelope carries only the
        shared fields — message/code/program/retry_after_s — not the
        structural detail like queue depths), so a wire-reconstructed
        error has the base contract but may lack subclass extras."""
        err = cls.__new__(cls)
        ServiceRetryableError.__init__(
            err, message, program=program, retry_after_s=retry_after_s
        )
        return err


class ServiceOverloadedError(ServiceRetryableError):
    """The serving layer's bounded request queue is at capacity: admission
    control rejects the request LOUDLY instead of growing the queue without
    bound (serve/queue.py). Callers should back off and resubmit; the
    "serve_rejected" counter tracks how often this fires. Carries `depth`
    (current) and `max_depth` (the configured admission bound), plus the
    ServiceRetryableError `program` / `retry_after_s` fields."""

    code = "overloaded"

    def __init__(self, depth, max_depth, program=None, retry_after_s=None):
        super().__init__(
            "serving queue at capacity (%d/%d): request rejected by "
            "admission control — back off and resubmit" % (depth, max_depth),
            program=program,
            retry_after_s=retry_after_s,
        )
        self.depth = depth
        self.max_depth = max_depth


class ServiceBrownoutError(ServiceRetryableError):
    """The serving layer is in BROWNOUT: quarantined executors cut the
    pool's capacity, or sustained queue pressure crossed the brownout
    threshold, and graded load-shedding (serve/health.BrownoutPolicy) is
    refusing this request's lane — bulk sheds first, interactive rides
    through to the hard admission bound. RETRIABLE by design: carries
    `retry_after_s`, the service's pressure-scaled hint for when capacity
    should be back (probation probes re-admitting devices, or the queue
    draining). Counted under "serve_shed_bulk"."""

    code = "brownout"

    def __init__(
        self,
        lane,
        retry_after_s,
        depth=None,
        capacity_fraction=None,
        program=None,
    ):
        detail = []
        if capacity_fraction is not None:
            detail.append("capacity %d%%" % round(capacity_fraction * 100))
        if depth is not None:
            detail.append("depth %d" % depth)
        super().__init__(
            "service brownout (%s): %s lane shed — retry after ~%.3gs"
            % (", ".join(detail) or "degraded", lane, retry_after_s),
            program=program,
            retry_after_s=retry_after_s,
        )
        self.lane = lane
        self.depth = depth
        self.capacity_fraction = capacity_fraction


class QuorumUnreachableError(ServiceRetryableError):
    """The threshold-issuance layer cannot assemble t distinct valid
    partial signatures for a request: too many authorities are crashed,
    hung, quarantined, or emitting corrupt partials (coconut_tpu/issue/).
    RETRIABLE by design — quorum loss is usually transient (authorities
    re-admit through the probation ladder; a hedged retry may land on a
    healthier pool). Carries `needed` (the threshold t), `have` (distinct
    valid partials collected), and `live` (authorities that could still
    contribute when the service gave up). Counted under
    "issue_quorum_unreachable"."""

    code = "quorum_unreachable"

    def __init__(self, needed, have, live=0, program=None, retry_after_s=None):
        super().__init__(
            "issuance quorum unreachable: have %d of %d required partial "
            "signatures with only %d live authorities left able to "
            "contribute — retry once the pool recovers" % (have, needed, live),
            program=program,
            retry_after_s=retry_after_s,
        )
        self.needed = needed
        self.have = have
        self.live = live


class ServiceClosedError(ServiceRetryableError):
    """A request was submitted to (or was still queued in) a credential
    service that is draining or shut down (serve/service.py). Futures of
    requests abandoned by a non-draining shutdown resolve with this
    exception so no caller ever hangs on a dropped future.

    RETRYABLE over the wire (PR 14): a closing replica is a fleet-level
    transient — some OTHER replica can serve the request right now, so
    the router's failover path must treat a closed-replica refusal like a
    transport failure and resubmit on a ring successor instead of
    surfacing a terminal error mid-restart. `retry_after_s` defaults to
    0.0 ("retry elsewhere immediately"); a single-replica caller with
    nowhere to fail over can still treat it as terminal by checking the
    `code`."""

    code = "closed"


class ShareVerificationError(GeneralError):
    """A Pedersen-committed share failed verification against its dealer's
    coefficient commitments (sss.PedersenVSS.verify_share), or a DVSS/DKG
    participant refused a structurally-invalid share (own share echoed
    back, duplicate dealer). Carries `dealer_id` — the authority whose
    sharing is at fault, the exact-attribution analogue of the issuance
    path's corrupt-partial naming — and `round`, the key-lifecycle round
    label ("dkg" / "refresh" / "reshare" / None for offline use) so
    complaints are auditable. NOT retriable: the same share can never
    start verifying; the dealer must be excluded."""

    code = "share_rejected"

    def __init__(self, message, dealer_id=None, round=None):
        super().__init__(message)
        self.dealer_id = dealer_id
        self.round = round


class DkgAbortedError(ServiceRetryableError):
    """A distributed key-generation (or proactive refresh / reshare) round
    could not complete: after excluding dealers named by share-verification
    complaints and dealers that were unreachable, fewer than `threshold`
    qualified dealers remain, so no key could be established
    (coconut_tpu/keylife/dkg.py). RETRIABLE — unreachable authorities
    usually return (probation ladder, restarts); a later round may
    succeed. Carries `needed` (the threshold t), `qualified` (dealers
    that survived complaints), and `excluded` (the sorted ids of dealers
    named by complaints or unreachable)."""

    code = "dkg_aborted"

    def __init__(
        self, needed, qualified, excluded=(), program=None, retry_after_s=None
    ):
        excluded = tuple(sorted(excluded))
        super().__init__(
            "DKG aborted: only %d of %d required qualified dealers remain "
            "(excluded: %s) — retry once the authority pool recovers"
            % (qualified, needed, list(excluded) or "none"),
            program=program,
            retry_after_s=retry_after_s,
        )
        self.needed = needed
        self.qualified = qualified
        self.excluded = excluded


class EpochUnknownError(CoconutError):
    """A request named a key epoch this service has never activated (or has
    not activated YET — a client racing ahead of a rollover). NOT blindly
    retriable: a future epoch may become valid after the rollover lands,
    but a fabricated epoch never will, and the service cannot tell which —
    callers should re-resolve the live epoch set from beacons and resubmit
    under an advertised epoch. Carries `epoch` and the `live` epoch ids
    known when refused. Counted under "keylife_epoch_unknown"."""

    code = "epoch_unknown"

    def __init__(self, epoch, live=()):
        super().__init__(
            "unknown key epoch %d: this service has epochs %s live — "
            "re-resolve the epoch set and resubmit" % (epoch, sorted(live))
        )
        self.epoch = epoch
        self.live = tuple(sorted(live))


class EpochRetiredError(CoconutError):
    """A request named a key epoch that existed but has been retired out of
    the bounded live window (keylife.EpochRegistry): its verkey is no
    longer served and credentials minted under it can no longer be
    verified here. NOT retriable — retirement is monotonic; the credential
    must be re-minted under a live epoch. Carries `epoch` and the `live`
    epoch ids. Counted under "keylife_epoch_retired"."""

    code = "epoch_retired"

    def __init__(self, epoch, live=()):
        super().__init__(
            "key epoch %d is retired: credentials minted under it must be "
            "re-minted (live epochs: %s)" % (epoch, sorted(live))
        )
        self.epoch = epoch
        self.live = tuple(sorted(live))


class TenantAuthError(CoconutError):
    """The gateway (coconut_tpu/net) rejected a request whose API key maps
    to no provisioned tenant. NOT retriable: resubmitting the same key
    can never succeed. Counted under "gateway_auth_failures"."""

    code = "tenant_auth"


class TenantQuotaError(CoconutError):
    """A tenant's absolute request quota is exhausted (net/tenant.py).
    NOT retriable within the quota epoch — unlike a token-bucket throttle
    there is no refill to wait for; the operator must raise the quota.
    Counted under "gateway_tenant_<id>_quota_rejected"."""

    code = "tenant_quota"

    def __init__(self, tenant, used, quota):
        super().__init__(
            "tenant %r quota exhausted (%d/%d requests): raise the quota "
            "or rotate the epoch" % (tenant, used, quota)
        )
        self.tenant = tenant
        self.used = used
        self.quota = quota


class TenantRateLimitError(ServiceRetryableError):
    """A tenant's token bucket is empty (net/tenant.py): the request was
    refused BEFORE engine admission. RETRIABLE — `retry_after_s` is the
    bucket's refill horizon for one token. Counted under
    "gateway_tenant_<id>_throttled"."""

    code = "tenant_rate_limited"

    def __init__(self, tenant, retry_after_s, program=None):
        super().__init__(
            "tenant %r rate-limited: token bucket empty — retry after "
            "~%.3gs" % (tenant, _finite_retry_after(retry_after_s)),
            program=program,
            retry_after_s=retry_after_s,
        )
        self.tenant = tenant


class DoubleSpendError(CoconutError):
    """A show-verify lane presented a credential whose nullifier is
    already in the replicated nullifier set (coconut_tpu/state) — the
    Coconut paper's e-cash/petition double-spend case. NOT retriable
    anywhere in the fleet: the nullifier is a deterministic digest of
    the proof transcript, so replaying the same show against any
    replica that has the fact (locally witnessed, WAL-replayed, or
    anti-entropy-replicated) yields the same rejection. Carries the
    `nullifier` hex digest, the `epoch` it is scoped to, and (PR 19)
    the application `domain` when the show was domain-scoped (petition
    campaign, e-cash — see state/nullifier.py). Counted under
    "nullifier_double_spends"."""

    code = "double_spend"

    # class-level defaults: error_from_wire reconstructs non-retryable
    # errors via cls.__new__ + CoconutError.__init__, which never runs
    # this subclass __init__ — attribute reads must still succeed
    nullifier = None
    epoch = None
    domain = None

    def __init__(self, nullifier=None, epoch=None, domain=None):
        super().__init__(
            "credential already shown: nullifier %s is spent%s%s"
            % (
                nullifier if nullifier is not None else "<unknown>",
                "" if epoch is None else " (epoch %d)" % epoch,
                "" if domain is None else " [domain %s]" % domain,
            )
        )
        self.nullifier = nullifier
        self.epoch = epoch
        self.domain = domain

    def _restore_wire_fields(self, message):
        # the envelope carries only (code, message); the message format
        # above is part of the wire contract, so the structured fields
        # survive the round trip — clients match on err.nullifier, not
        # on message text
        m = re.search(
            r"nullifier ([0-9a-f]{64}) is spent"
            r"(?: \(epoch (\d+)\))?(?: \[domain ([^\]]+)\])?",
            message,
        )
        if m is not None:
            self.nullifier = m.group(1)
            self.epoch = None if m.group(2) is None else int(m.group(2))
            self.domain = m.group(3)


#: the 1:1 code <-> class map the wire error envelope encodes/decodes
#: through (net/wire.py). Retriable codes reconstruct via `from_wire`
#: (shared fields only); the rest rebuild with their message.
WIRE_ERROR_CODES = {
    cls.code: cls
    for cls in (
        GeneralError,
        DeserializationError,
        UnsupportedNoOfMessages,
        UnequalNoOfBasesExponents,
        PSError,
        TransientBackendError,
        ServiceRetryableError,
        ServiceOverloadedError,
        ServiceBrownoutError,
        QuorumUnreachableError,
        ServiceClosedError,
        TenantAuthError,
        TenantQuotaError,
        TenantRateLimitError,
        ShareVerificationError,
        DkgAbortedError,
        EpochUnknownError,
        EpochRetiredError,
        DoubleSpendError,
    )
}


def error_from_wire(code, message, program=None, retry_after_s=0.0):
    """Rebuild the typed exception a wire error envelope describes.
    Unknown codes degrade to GeneralError (forward compatibility: a newer
    server may emit codes this client predates) with the code preserved
    as an instance attribute so nothing is lost."""
    cls = WIRE_ERROR_CODES.get(code)
    if cls is None:
        err = GeneralError(message)
        err.code = code
        return err
    if issubclass(cls, ServiceRetryableError):
        return cls.from_wire(
            message, program=program, retry_after_s=retry_after_s
        )
    err = cls.__new__(cls)
    CoconutError.__init__(err, message)
    if program is not None:
        err.program = program
    restore = getattr(err, "_restore_wire_fields", None)
    if restore is not None:
        restore(message)
    return err


class CheckpointCorruptError(CoconutError):
    """A stream checkpoint file failed integrity validation: truncated or
    unparseable bytes, an unknown schema version, or a CRC mismatch.
    stream.StreamState catches this internally, quarantines the file aside
    (`<path>.corrupt*`) and restarts cleanly — it must never surface as a
    bare json.JSONDecodeError mid-resume."""


class CheckpointMismatchError(CoconutError):
    """A structurally-valid checkpoint belongs to a DIFFERENT run: its
    stored run-config fingerprint (result mode + verkey digest —
    stream.run_fingerprint) disagrees with the resuming run's. Unlike
    corruption this fails loudly instead of quarantining: silently resuming
    the wrong run would produce tallies for a stream nobody asked about."""

    def __init__(self, stored, expected):
        super().__init__(
            "checkpoint fingerprint %s does not match this run's %s: "
            "refusing to resume a different run's state (delete or move "
            "the state file to start over)" % (stored, expected)
        )
        self.stored = stored
        self.expected = expected
