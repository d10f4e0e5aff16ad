// Zero-copy device->wire window put path (XLA FFI custom calls).
//
// The PR-9 native transport already runs the coalesce/encode/send loop in
// C++, but every put still staged its payload through the Python host:
// jax.device_get of the whole tensor, a Python per-edge loop, and a
// bytes/buffer-protocol hop into bf_wintx_send.  This unit removes the
// host round-trip: a put dispatch is compiled once into a PLAN (per-edge
// peer endpoint, wire op, weight, row offset), and executing the plan
// walks the caller's f32 buffer IN PLACE, encoding each row straight into
// the bf_wintx per-peer arenas — one arena copy total, no host staging
// copy anywhere.
//
// Two entries share one executor (PlanRun):
//   * bf_xla_plan_run      — eager: ops/window.py extracts the XLA buffer
//                            pointer (CPU backend: device memory IS host
//                            memory) and calls in over ctypes;
//   * bf_xla_win_put       — the XLA FFI handler (registered through
//                            jax.ffi): the same put
//                            lowered INTO a compiled program, so an
//                            optimizer step can issue its puts while XLA
//                            is still executing the rest of the program.
//                            Compiled only when the jaxlib FFI headers
//                            were present (BF_HAVE_XLA_FFI); the Python
//                            side probes bf_xla_has_handler().
//
// Codecs mirror ops/window._send_to_proc bit-for-bit where determinism
// allows: dense rows ship raw (the edge weight rides the wire header and
// the receiver scales — same contract as the Python remote-edge path),
// bf16 uses round-to-nearest-even (numpy/ml_dtypes' astype rule), and
// sparse:<frac> keeps sender-side error-feedback residuals keyed by
// (window, src, dst) — the same key and purge points as the Python
// _ef_residuals dict, so wire-mass + residual == input-mass holds on this
// path too.  Top-k tie-breaking is (|v| desc, index asc); numpy's
// argpartition breaks ties arbitrarily, so bit-identity across paths is
// guaranteed for distinct magnitudes (ties differ only in WHICH equal
// values ship — the shipped mass is the same).
//
// The tx handle rides each call (an i64 attribute of the FFI custom
// call) rather than any ambient global, so multiple transports in one
// process (loopback tests: server + client) stay unambiguous.  Lifetime:
// the same exposure as every other bf_wintx_* ctypes call — the Python
// side nulls its handle before bf_wintx_stop, and bf_wintx_send itself
// is safe against a concurrent stop (inflight guard + stopping flag).

#include "bluefog_native.h"

#include <cmath>
#include <cstring>

#include <algorithm>
#include <atomic>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <tuple>
#include <unordered_map>
#include <vector>

namespace {

constexpr uint8_t kXFlagBf16 = 0x40;    // OP_BF16_FLAG (ops/transport.py)
constexpr uint8_t kXFlagSparse = 0x20;  // OP_SPARSE_FLAG
constexpr uint8_t kXFlagTrace = 0x10;   // OP_TRACE_FLAG

struct XEdge {
  std::string host;
  int32_t port = 0;
  uint8_t op = 0;
  int32_t src = 0;
  int32_t dst = 0;
  double weight = 0.0;
  double p_weight = 0.0;
  int64_t row = 0;
  int32_t stripe = 0;  // transport stripe, pinned at plan-compile time
};

struct XPlan {
  std::string name;
  int64_t elems = 0;
  int32_t codec = 0;  // 0 dense, 1 bf16, 2 sparse
  double frac = 1.0;
  std::vector<XEdge> edges;
};

std::mutex g_plan_m;
std::unordered_map<int64_t, std::shared_ptr<XPlan>>* g_plans =
    new std::unordered_map<int64_t, std::shared_ptr<XPlan>>();
int64_t g_next_plan = 1;

// Sparse error-feedback residuals, keyed (window, src, dst) — the native
// twin of ops/window._ef_residuals (same key, same purge points), so the
// time-summed wire traffic on this path carries the full input mass.
std::mutex g_res_m;
std::map<std::tuple<std::string, int32_t, int32_t>, std::vector<float>>*
    g_res = new std::map<std::tuple<std::string, int32_t, int32_t>,
                         std::vector<float>>();

// f32 -> bf16 with round-to-nearest-even: the rule numpy/ml_dtypes'
// astype(bfloat16) applies, so bf16 frames are bit-identical to the
// Python encoder's for every finite value (NaNs quieten canonically).
inline uint16_t Bf16RNE(float f) {
  uint32_t u;
  std::memcpy(&u, &f, 4);
  if ((u & 0x7fffffffu) > 0x7f800000u)           // NaN: keep it a NaN
    return (uint16_t)((u >> 16) | 0x0040u);
  uint32_t bias = 0x7fffu + ((u >> 16) & 1u);    // ties to even
  return (uint16_t)((u + bias) >> 16);
}

std::shared_ptr<XPlan> FindPlan(int64_t id) {
  std::lock_guard<std::mutex> lk(g_plan_m);
  auto it = g_plans->find(id);
  return it == g_plans->end() ? nullptr : it->second;
}

// Encode + enqueue one sparse edge: v = row + residual, ship the top
// ceil(frac*elems) entries by |v| (ascending index order, bit-exact f32
// values), keep the complement as the new residual.  Mirrors
// ops/window._sparse_payload.
int32_t SendSparse(bf_wintx_t* tx, const XPlan& p, const XEdge& e,
                   const float* row) {
  thread_local std::vector<float> v;
  thread_local std::vector<int32_t> order;
  thread_local std::vector<uint8_t> payload;
  const int64_t n = p.elems;
  v.resize((size_t)n);
  {
    std::lock_guard<std::mutex> lk(g_res_m);
    auto it = g_res->find(std::make_tuple(p.name, e.src, e.dst));
    if (it != g_res->end() && (int64_t)it->second.size() == n) {
      for (int64_t i = 0; i < n; ++i) v[(size_t)i] = row[i] + it->second[(size_t)i];
    } else {
      std::memcpy(v.data(), row, (size_t)n * 4);
    }
  }
  int64_t k = (int64_t)std::ceil(p.frac * (double)n);
  if (k < 1) k = 1;
  if (k > n) k = n;
  order.resize((size_t)n);
  for (int64_t i = 0; i < n; ++i) order[(size_t)i] = (int32_t)i;
  if (k < n) {
    // Top-k by |v|, deterministic (|v| desc, index asc on ties), then
    // ascending index — the order the Python encoder ships.
    std::nth_element(order.begin(), order.begin() + k, order.end(),
                     [&](int32_t a, int32_t b) {
                       float fa = std::fabs(v[(size_t)a]);
                       float fb = std::fabs(v[(size_t)b]);
                       if (fa != fb) return fa > fb;
                       return a < b;
                     });
    std::sort(order.begin(), order.begin() + k);
  }
  payload.resize(4 + (size_t)k * 8);
  uint32_t k32 = (uint32_t)k;
  std::memcpy(payload.data(), &k32, 4);
  uint8_t* ip = payload.data() + 4;
  uint8_t* vp = payload.data() + 4 + (size_t)k * 4;
  for (int64_t j = 0; j < k; ++j) {
    int32_t idx = order[(size_t)j];
    std::memcpy(ip + 4 * j, &idx, 4);
    std::memcpy(vp + 4 * j, &v[(size_t)idx], 4);
  }
  {
    // New residual: v with the shipped entries zeroed.
    std::lock_guard<std::mutex> lk(g_res_m);
    auto& res = (*g_res)[std::make_tuple(p.name, e.src, e.dst)];
    res.assign(v.begin(), v.end());
    for (int64_t j = 0; j < k; ++j) res[(size_t)order[(size_t)j]] = 0.0f;
  }
  uint8_t op = (uint8_t)(e.op | kXFlagSparse);
  uint8_t trailer[BF_TRACE_TRAILER_LEN];
  if (bf_trace_next(e.src, trailer)) {
    // Wire trace tag: the trailer rides INSIDE the payload (after the
    // sparse stream), exactly as the Python encoder appends it, so the
    // receiver strips it identically whichever path sent the row.
    payload.insert(payload.end(), trailer, trailer + BF_TRACE_TRAILER_LEN);
    op |= kXFlagTrace;
  }
  return bf_wintx_send(tx, e.host.c_str(), e.port, op, p.name.c_str(),
                       e.src, e.dst, e.weight, e.p_weight, payload.data(),
                       payload.size(), 0, e.stripe);
}

int32_t PlanRun(int64_t plan, const void* txp, const float* data,
                uint64_t total_elems) {
  auto p = FindPlan(plan);
  if (!p || txp == nullptr || data == nullptr) return -9;
  auto* tx = (bf_wintx_t*)(uintptr_t)txp;
  thread_local std::vector<uint16_t> half;
  thread_local std::vector<uint8_t> tagged;
  uint8_t trailer[BF_TRACE_TRAILER_LEN];
  for (const XEdge& e : p->edges) {
    if (e.row < 0 ||
        (uint64_t)(e.row + 1) * (uint64_t)p->elems > total_elems)
      return -10;
    const float* row = data + (size_t)e.row * (size_t)p->elems;
    int32_t rc;
    if (p->codec == 2) {
      rc = SendSparse(tx, *p, e, row);
    } else if (p->codec == 1) {
      half.resize((size_t)p->elems);
      for (int64_t i = 0; i < p->elems; ++i) half[(size_t)i] = Bf16RNE(row[i]);
      const uint8_t* body = (const uint8_t*)half.data();
      uint64_t blen = (uint64_t)p->elems * 2;
      uint8_t op = (uint8_t)(e.op | kXFlagBf16);
      if (bf_trace_next(e.src, trailer)) {
        tagged.assign(body, body + blen);
        tagged.insert(tagged.end(), trailer,
                      trailer + BF_TRACE_TRAILER_LEN);
        body = tagged.data();
        blen = tagged.size();
        op |= kXFlagTrace;
      }
      rc = bf_wintx_send(tx, e.host.c_str(), e.port, op, p->name.c_str(),
                         e.src, e.dst, e.weight, e.p_weight, body, blen, 0,
                         e.stripe);
    } else {
      // Dense: the row pointer goes straight into the arena copy — the
      // zero-staging-copy fast path (the weight rides the wire header;
      // the receiver scales, exactly like the Python remote-edge path).
      // A sampled trace tag is the one exception: the trailer must ride
      // the payload, so that 1-in-N message pays one staging copy.
      const uint8_t* body = (const uint8_t*)row;
      uint64_t blen = (uint64_t)p->elems * 4;
      uint8_t op = e.op;
      if (bf_trace_next(e.src, trailer)) {
        tagged.assign(body, body + blen);
        tagged.insert(tagged.end(), trailer,
                      trailer + BF_TRACE_TRAILER_LEN);
        body = tagged.data();
        blen = tagged.size();
        op |= kXFlagTrace;
      }
      rc = bf_wintx_send(tx, e.host.c_str(), e.port, op, p->name.c_str(),
                         e.src, e.dst, e.weight, e.p_weight, body, blen, 0,
                         e.stripe);
    }
    if (rc != 0) return rc;  // first failing edge stops the dispatch
  }
  return 0;
}

}  // namespace

extern "C" {

int64_t bf_xla_plan_new(const char* name, int64_t elems, int32_t n_edges,
                        int32_t codec, double sparse_frac) {
  if (!name || elems <= 0 || n_edges < 0) return -9;
  if (std::strlen(name) >= 128) return -4;
  auto p = std::make_shared<XPlan>();
  p->name = name;
  p->elems = elems;
  p->codec = codec;
  p->frac = sparse_frac;
  p->edges.resize((size_t)n_edges);
  std::lock_guard<std::mutex> lk(g_plan_m);
  int64_t id = g_next_plan++;
  (*g_plans)[id] = std::move(p);
  return id;
}

int32_t bf_xla_plan_edge(int64_t plan, int32_t i, const char* host,
                         int32_t port, uint8_t op, int32_t src, int32_t dst,
                         double weight, int64_t row, int32_t stripe) {
  auto p = FindPlan(plan);
  if (!p || !host || i < 0 || (size_t)i >= p->edges.size()) return -9;
  XEdge& e = p->edges[(size_t)i];
  e.host = host;
  e.port = port;
  e.op = op;
  e.src = src;
  e.dst = dst;
  e.weight = weight;
  e.row = row;
  e.stripe = stripe < 0 ? 0 : stripe;
  return 0;
}

int32_t bf_xla_plan_set_p(int64_t plan, const double* p_vals, int32_t n) {
  auto p = FindPlan(plan);
  if (!p || !p_vals || (size_t)n != p->edges.size()) return -9;
  for (int32_t i = 0; i < n; ++i) p->edges[(size_t)i].p_weight = p_vals[i];
  return 0;
}

int32_t bf_xla_plan_run(int64_t plan, const void* tx, const float* data,
                        uint64_t total_elems) {
  return PlanRun(plan, tx, data, total_elems);
}

int32_t bf_xla_plan_free(int64_t plan) {
  std::lock_guard<std::mutex> lk(g_plan_m);
  return g_plans->erase(plan) ? 0 : -9;
}

int64_t bf_xla_take_residual(const char* name, int32_t src, int32_t dst,
                             float* out, int64_t cap) {
  if (!name || !out) return 0;
  std::lock_guard<std::mutex> lk(g_res_m);
  auto it = g_res->find(std::make_tuple(std::string(name), src, dst));
  if (it == g_res->end()) return 0;
  int64_t n = (int64_t)it->second.size();
  if (n > cap) return -1;
  std::memcpy(out, it->second.data(), (size_t)n * 4);
  g_res->erase(it);
  return n;
}

int32_t bf_xla_add_residual(const char* name, int32_t src, int32_t dst,
                            const float* data, int64_t n) {
  if (!name || !data || n <= 0) return -9;
  std::lock_guard<std::mutex> lk(g_res_m);
  auto& res = (*g_res)[std::make_tuple(std::string(name), src, dst)];
  if ((int64_t)res.size() != n) res.assign((size_t)n, 0.0f);
  for (int64_t i = 0; i < n; ++i) res[(size_t)i] += data[i];
  return 0;
}

void bf_xla_drop_residuals(const char* name) {
  std::lock_guard<std::mutex> lk(g_res_m);
  if (name == nullptr) {
    g_res->clear();
    return;
  }
  std::string want(name);
  for (auto it = g_res->begin(); it != g_res->end();) {
    if (std::get<0>(it->first) == want)
      it = g_res->erase(it);
    else
      ++it;
  }
}

}  // extern "C"

// ---------------------------------------------------------------------------
// XLA FFI handler (compiled only when the jaxlib FFI headers are present)
// ---------------------------------------------------------------------------

#ifdef BF_HAVE_XLA_FFI

// The bundled jaxlib headers trip -Wreturn-type / -Wunused-parameter in
// their own helpers (some at template-instantiation sites); the Makefile
// scopes the matching -Wno-* waivers to this one object so the rest of
// the native build stays pledged -Wall -Wextra clean.
#include "xla/ffi/api/ffi.h"

namespace bffi = xla::ffi;

static bffi::Error BfXlaWinPutImpl(bffi::AnyBuffer x,
                                   bffi::Result<bffi::AnyBuffer> status,
                                   int64_t plan_id, int64_t tx) {
  auto* out = reinterpret_cast<int32_t*>(status->untyped_data());
  if (status->element_count() < 1)
    return bffi::Error(bffi::ErrorCode::kInvalidArgument,
                       "bf_xla_win_put needs an i32[1] status output");
  if (x.element_type() != bffi::DataType::F32) {
    out[0] = -12;  // non-f32 buffer: the Python side falls back
    return bffi::Error::Success();
  }
  // Status rides the output buffer (the dispatcher raises on nonzero)
  // instead of an FFI error: a backpressure/peer failure is a transport
  // condition the window op owns, not an XLA program failure.
  out[0] = PlanRun(plan_id, (const void*)(uintptr_t)tx,
                   reinterpret_cast<const float*>(x.untyped_data()),
                   (uint64_t)x.element_count());
  return bffi::Error::Success();
}

XLA_FFI_DEFINE_HANDLER_SYMBOL(bf_xla_win_put, BfXlaWinPutImpl,
                              bffi::Ffi::Bind()
                                  .Arg<bffi::AnyBuffer>()
                                  .Ret<bffi::AnyBuffer>()
                                  .Attr<int64_t>("plan_id")
                                  .Attr<int64_t>("tx"));

extern "C" int32_t bf_xla_has_handler(void) { return 1; }

#else  // !BF_HAVE_XLA_FFI

extern "C" int32_t bf_xla_has_handler(void) { return 0; }

#endif  // BF_HAVE_XLA_FFI
