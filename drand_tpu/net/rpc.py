"""Descriptor-driven gRPC plumbing.

The image ships grpcio + protoc but not grpcio-tools, so instead of
generated *_pb2_grpc stubs this module derives servicers and client stubs
directly from the protobuf service descriptors — one code path for all
three drand services (Protocol, Public, Control), always in sync with the
.proto files.
"""

from __future__ import annotations

import os

import grpc
from google.protobuf import message_factory

from drand_tpu.protogen import drand_pb2

_SERVICES = drand_pb2.DESCRIPTOR.services_by_name


def _msg_class(desc):
    return message_factory.GetMessageClass(desc)


def _methods(service_name: str):
    svc = _SERVICES[service_name]
    for m in svc.methods:
        yield m.name, _msg_class(m.input_type), _msg_class(m.output_type), \
            m.server_streaming


def _version_ok(req) -> bool:
    """Server-side node-version compatibility gate (the reference's
    NodeVersionValidator interceptor, `net/listener.go:55-58` +
    `core/drand_daemon_interceptors.go:18-60`): requests carrying metadata
    with a node_version must match our major.minor; requests without
    metadata pass (the reference lets them through too)."""
    if os.environ.get("DISABLE_VERSION_CHECK") == "1":
        return True
    try:
        md = getattr(req, "metadata", None)
        if md is None or not md.HasField("node_version"):
            return True
        v = md.node_version
    except Exception:
        return True
    from drand_tpu.common import VERSION
    return v.major == VERSION.major and v.minor == VERSION.minor


_VERSION_ERR = "incompatible node version"


def service_handler(service_name: str, impl,
                    validate_version: bool = False) -> grpc.GenericRpcHandler:
    """Build a generic handler for `impl`, an object with async methods
    named after the service's RPCs (missing methods -> UNIMPLEMENTED).

    validate_version=True wraps every method with the node-version gate
    (used on the private gateway's Protocol/Public services, matching the
    reference's interceptor placement)."""
    handlers = {}
    for name, req_cls, _resp, streaming in _methods(service_name):
        fn = getattr(impl, name, None)
        if fn is None:
            continue
        if validate_version:
            fn = _with_version_check(fn, streaming)
        # outermost: server-side tracing span re-rooted from the
        # caller's trace context in request metadata — even
        # version-rejected requests leave an error span behind
        fn = _with_server_span(fn, service_name, name, streaming)
        if streaming:
            handlers[name] = grpc.unary_stream_rpc_method_handler(
                fn, request_deserializer=req_cls.FromString,
                response_serializer=lambda m: m.SerializeToString())
        else:
            handlers[name] = grpc.unary_unary_rpc_method_handler(
                fn, request_deserializer=req_cls.FromString,
                response_serializer=lambda m: m.SerializeToString())
    return grpc.method_handlers_generic_handler(
        f"drand.{service_name}", handlers)


def _req_round(req) -> int | None:
    """The round a request addresses, when it names one (span attr)."""
    r = getattr(req, "round", 0) or getattr(req, "from_round", 0)
    return int(r) if r else None


def _with_server_span(fn, service: str, method: str, streaming: bool):
    """Wrap a service method in a tracing.server_span: the span adopts
    the caller's (trace_id, span_id) from the request `metadata` field —
    the same field the version gate below reads — so spans opened while
    handling the RPC parent to the caller's span across the wire."""
    from drand_tpu import tracing
    span_name = f"rpc.{service}.{method}"
    if streaming:
        async def stream_traced(req, ctx):
            with tracing.server_span(span_name,
                                     getattr(req, "metadata", None),
                                     round_=_req_round(req)) as sp:
                try:
                    async for item in fn(req, ctx):
                        yield item
                except tracing.STREAM_CLOSED:
                    # a bounded catch-up does, at its `up_to`
                    sp.end("closed")
                    raise
        return stream_traced

    async def unary_traced(req, ctx):
        with tracing.server_span(span_name, getattr(req, "metadata", None),
                                 round_=_req_round(req)):
            return await fn(req, ctx)
    return unary_traced


def _with_version_check(fn, streaming: bool):
    if streaming:
        async def stream_wrapped(req, ctx):
            if not _version_ok(req):
                await ctx.abort(grpc.StatusCode.FAILED_PRECONDITION,
                                _VERSION_ERR)
            async for item in fn(req, ctx):
                yield item
        return stream_wrapped

    async def unary_wrapped(req, ctx):
        if not _version_ok(req):
            await ctx.abort(grpc.StatusCode.FAILED_PRECONDITION, _VERSION_ERR)
        return await fn(req, ctx)
    return unary_wrapped


class ServiceStub:
    """Client stub over a grpc.aio channel, methods resolved on attribute
    access: `stub.PartialBeacon(req, timeout=...)`."""

    def __init__(self, channel: "grpc.aio.Channel", service_name: str):
        self._channel = channel
        self._service = service_name
        self._cache = {}
        self._meta = {n: (req, resp, stream)
                      for n, req, resp, stream in _methods(service_name)}

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        if name not in self._meta:
            raise AttributeError(f"{self._service} has no RPC {name}")
        if name not in self._cache:
            req_cls, resp_cls, streaming = self._meta[name]
            path = f"/drand.{self._service}/{name}"
            if streaming:
                self._cache[name] = self._channel.unary_stream(
                    path, request_serializer=lambda m: m.SerializeToString(),
                    response_deserializer=resp_cls.FromString)
            else:
                self._cache[name] = self._channel.unary_unary(
                    path, request_serializer=lambda m: m.SerializeToString(),
                    response_deserializer=resp_cls.FromString)
        return self._cache[name]
