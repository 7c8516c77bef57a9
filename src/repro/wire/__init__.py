"""Unified OCTOPUS wire protocol (the client↔server interface).

  payload  — CodePayload: THE versioned carrier crossing the network —
             packed uint32 words, per-record streams, codebook version,
             measured nbytes (the single §2.8 accounting), optional
             label channels, and the §2.5 ``privatized`` invariant
  codec    — fused CodePayload -> feature decode (one dispatch per
             codebook-version group; record/phase bookkeeping lives here)
  session  — OctopusClient.round(batch) / OctopusServer.ingest(payload)
             + .features(): the session facades subsuming the PR-1..4
             function zoo (client_transmit, client_round_fused,
             unpack_transmission, hand-wired store/registry plumbing).
             ``ingest`` answers with a structured AdmissionResult
             verdict (accepted / migrated / deferred / rejected)
"""
from .codec import decode_payloads, decode_rows
from .payload import (DEFAULT_TASK, SUPPORTED_WIRE_VERSIONS, WIRE_VERSION,
                      CodePayload, as_payload, concat_payloads,
                      normalize_labels, payload_crc)
from .session import (ADMISSION_VERDICTS, TRANSIENT_REASONS,
                      AdmissionResult, OctopusClient, OctopusServer,
                      RetryPolicy, round_words)

__all__ = ["ADMISSION_VERDICTS", "AdmissionResult", "CodePayload",
           "OctopusClient", "OctopusServer", "RetryPolicy",
           "SUPPORTED_WIRE_VERSIONS", "TRANSIENT_REASONS", "WIRE_VERSION",
           "DEFAULT_TASK", "as_payload", "concat_payloads",
           "decode_payloads", "decode_rows", "normalize_labels",
           "payload_crc", "round_words"]
