// tm_native — native host-side hot paths for the TPU verification engine.
//
// The framework's compute path is JAX/XLA on the device; this module is the
// native runtime seam around it (SURVEY.md §2: the batch verification
// engine's host half): the per-batch packing that turns 10k signature
// triples into kernel input arrays, and RFC-6962 merkle hashing for part
// sets / block data. The wire decodes that sit on a request's path have a
// single-pass, GIL-released fast path here too, each answering None where
// the Python walk that specifies it must decide: commit_decode_columns
// (Commit.decode) and valset_decode_columns (ValidatorSet.decode).
// The entries on a verified commit's path time their own sections (gil::Free
// below): how long the work ran without the interpreter lock and how long
// the thread then waited to win it back, or, for a section too short to be
// worth a hand-over, that it kept the lock.
// CPython C API (no pybind11 in this image), built by g++ on the first
// tendermint_tpu.native.load().

#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <atomic>
#include <cstdint>
#include <cstring>
#include <sched.h>
#include <stdlib.h>
#include <thread>
#include <time.h>
#include <vector>

// --------------------------------------------------------------------------
// GIL-free sections that time themselves.
//
// GIL_FREE_BEGIN(ENTRY) ...work... GIL_FREE_END is Py_BEGIN_ALLOW_THREADS /
// Py_END_ALLOW_THREADS with three reads of CLOCK_MONOTONIC — the clock of
// time.perf_counter on Linux, so of the span tracer and, through the
// benchmark's marks, of the device trace: t_released just after the GIL
// is given up, t_wanted just before PyEval_RestoreThread, t_got just after
// it. With one thread calling, RestoreThread returns at once; with many it
// is a contest the thread may lose for a switch interval or more, and a
// span around the call reads that wait as the work it interrupted.
//
// GIL_HELD_IF(ENTRY, held) is the same block asked not to let go where
// `held` is true: a section of a microsecond, or of 20, hands the lock to
// a waiter who is awake only in time for the NEXT release, and its thread
// then queues behind every other caller to get it back (PERF.md §6, PR 37
// and PR 38). Such a section reads the same clock around its work, keeps
// its entry in last_sections() and waits for nothing: t_got == t_wanted.
// Whether a section holds is decided by the size of its input, inside the
// entry, and nowhere else.
//
//   gil_stats()      {entry: (sections, free_s, wait_s, held)}:
//                    process-wide, always on (three relaxed adds a section
//                    that let go, one for one that held), only rises;
//                    sections, free = wanted - released and wait = got -
//                    wanted are of the sections that gave the GIL up,
//                    held counts those that kept it
//   last_sections()  [(t_released, t_wanted, t_got, held), ...] in
//                    perf_counter seconds: the calling thread's last call
//                    of an entry below, one tuple a section (reset where
//                    the entry starts; at most MAX_SECTIONS)
namespace gil {

enum Entry {
  COMMIT_DECODE_COLUMNS,
  VALSET_DECODE_COLUMNS,
  COMMIT_PREP_FUSED,
  ED25519_RLC_PREP,
  SR25519_CHALLENGES,
  N_ENTRIES
};
static const char *const ENTRY_NAMES[N_ENTRIES] = {
    "commit_decode_columns", "valset_decode_columns", "commit_prep_fused",
    "ed25519_rlc_prep", "sr25519_challenges_buf"};
static const int MAX_SECTIONS = 4;

struct Stats {
  std::atomic<uint64_t> sections{0}, free_ns{0}, wait_ns{0}, held{0};
};
static Stats stats[N_ENTRIES];

struct Last {
  int n;
  int64_t t[MAX_SECTIONS][3];
  bool held[MAX_SECTIONS];
};
static thread_local Last last = {0, {}, {}};

static inline int64_t now_ns() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (int64_t)ts.tv_sec * 1000000000LL + ts.tv_nsec;
}

// An entry's first statement: the thread's sections are this call's.
static inline void enter() { last.n = 0; }

class Free {
  PyThreadState *save_;  // null: this section holds the GIL
  Entry entry_;
  int64_t released_;

 public:
  explicit Free(Entry e, bool held = false)
      : save_(held ? nullptr : PyEval_SaveThread()), entry_(e) {
    released_ = now_ns();
  }
  Free(const Free &) = delete;
  Free &operator=(const Free &) = delete;
  ~Free() {
    int64_t wanted = now_ns(), got = wanted;
    Stats &s = stats[entry_];
    if (save_) {
      PyEval_RestoreThread(save_);
      got = now_ns();
      s.sections.fetch_add(1, std::memory_order_relaxed);
      s.free_ns.fetch_add((uint64_t)(wanted - released_),
                          std::memory_order_relaxed);
      s.wait_ns.fetch_add((uint64_t)(got - wanted),
                          std::memory_order_relaxed);
    } else {
      s.held.fetch_add(1, std::memory_order_relaxed);
    }
    if (last.n < MAX_SECTIONS) {
      last.held[last.n] = !save_;
      int64_t *t = last.t[last.n++];
      t[0] = released_;
      t[1] = wanted;
      t[2] = got;
    }
  }
};

// The pair that takes Py_BEGIN_ALLOW_THREADS / Py_END_ALLOW_THREADS' place
// (a block, as theirs is); GIL_HELD_IF opens the same block and keeps the
// GIL through it where `held` is true
#define GIL_FREE_BEGIN(entry) { gil::Free gil_free_(gil::entry);
#define GIL_HELD_IF(entry, held) { gil::Free gil_free_(gil::entry, (held));
#define GIL_FREE_END }

// time.perf_counter's own conversion (pytime.c _PyTime_AsSecondsDouble):
// a reading here and one there of the same instant are the same double
static double seconds(int64_t ns) {
  volatile double d;
  if (ns % 1000000000LL == 0) {
    d = (double)(ns / 1000000000LL);
  } else {
    d = (double)ns;
    d /= 1e9;
  }
  return d;
}

}  // namespace gil

static PyObject *py_gil_stats(PyObject *, PyObject *) {
  PyObject *out = PyDict_New();
  if (!out) return nullptr;
  for (int e = 0; e < gil::N_ENTRIES; e++) {
    const gil::Stats &s = gil::stats[e];
    PyObject *v = Py_BuildValue(
        "(KddK)",
        (unsigned long long)s.sections.load(std::memory_order_relaxed),
        (double)s.free_ns.load(std::memory_order_relaxed) / 1e9,
        (double)s.wait_ns.load(std::memory_order_relaxed) / 1e9,
        (unsigned long long)s.held.load(std::memory_order_relaxed));
    if (!v || PyDict_SetItemString(out, gil::ENTRY_NAMES[e], v) < 0) {
      Py_XDECREF(v);
      Py_DECREF(out);
      return nullptr;
    }
    Py_DECREF(v);
  }
  return out;
}

static PyObject *py_last_sections(PyObject *, PyObject *) {
  const gil::Last &l = gil::last;
  PyObject *out = PyList_New(l.n);
  if (!out) return nullptr;
  for (int i = 0; i < l.n; i++) {
    PyObject *v = Py_BuildValue("(dddO)", gil::seconds(l.t[i][0]),
                                gil::seconds(l.t[i][1]),
                                gil::seconds(l.t[i][2]),
                                l.held[i] ? Py_True : Py_False);
    if (!v) {
      Py_DECREF(out);
      return nullptr;
    }
    PyList_SET_ITEM(out, i, v);
  }
  return out;
}

// --------------------------------------------------------------------------
// SHA-256 (FIPS 180-4), self-contained.

namespace sha256 {

static const uint32_t K[64] = {
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2};

static inline uint32_t rotr(uint32_t x, int n) {
  return (x >> n) | (x << (32 - n));
}

struct Ctx {
  uint32_t h[8];
  uint64_t len;
  uint8_t buf[64];
  size_t buflen;
};

static void init(Ctx *c) {
  static const uint32_t iv[8] = {0x6a09e667, 0xbb67ae85, 0x3c6ef372,
                                 0xa54ff53a, 0x510e527f, 0x9b05688c,
                                 0x1f83d9ab, 0x5be0cd19};
  memcpy(c->h, iv, sizeof(iv));
  c->len = 0;
  c->buflen = 0;
}

static void compress(Ctx *c, const uint8_t *p) {
  uint32_t w[64];
  for (int i = 0; i < 16; i++)
    w[i] = (uint32_t(p[4 * i]) << 24) | (uint32_t(p[4 * i + 1]) << 16) |
           (uint32_t(p[4 * i + 2]) << 8) | uint32_t(p[4 * i + 3]);
  for (int i = 16; i < 64; i++) {
    uint32_t s0 = rotr(w[i - 15], 7) ^ rotr(w[i - 15], 18) ^ (w[i - 15] >> 3);
    uint32_t s1 = rotr(w[i - 2], 17) ^ rotr(w[i - 2], 19) ^ (w[i - 2] >> 10);
    w[i] = w[i - 16] + s0 + w[i - 7] + s1;
  }
  uint32_t a = c->h[0], b = c->h[1], cc = c->h[2], d = c->h[3], e = c->h[4],
           f = c->h[5], g = c->h[6], h = c->h[7];
  for (int i = 0; i < 64; i++) {
    uint32_t S1 = rotr(e, 6) ^ rotr(e, 11) ^ rotr(e, 25);
    uint32_t ch = (e & f) ^ (~e & g);
    uint32_t t1 = h + S1 + ch + K[i] + w[i];
    uint32_t S0 = rotr(a, 2) ^ rotr(a, 13) ^ rotr(a, 22);
    uint32_t maj = (a & b) ^ (a & cc) ^ (b & cc);
    uint32_t t2 = S0 + maj;
    h = g; g = f; f = e; e = d + t1;
    d = cc; cc = b; b = a; a = t1 + t2;
  }
  c->h[0] += a; c->h[1] += b; c->h[2] += cc; c->h[3] += d;
  c->h[4] += e; c->h[5] += f; c->h[6] += g; c->h[7] += h;
}

static void update(Ctx *c, const uint8_t *data, size_t n) {
  c->len += n;
  if (c->buflen) {
    size_t take = 64 - c->buflen;
    if (take > n) take = n;
    memcpy(c->buf + c->buflen, data, take);
    c->buflen += take;
    data += take;
    n -= take;
    if (c->buflen == 64) {
      compress(c, c->buf);
      c->buflen = 0;
    }
  }
  while (n >= 64) {
    compress(c, data);
    data += 64;
    n -= 64;
  }
  if (n) {
    memcpy(c->buf, data, n);
    c->buflen = n;
  }
}

static void final(Ctx *c, uint8_t out[32]) {
  uint64_t bitlen = c->len * 8;
  uint8_t pad = 0x80;
  update(c, &pad, 1);
  uint8_t z = 0;
  while (c->buflen != 56) update(c, &z, 1);
  uint8_t lenb[8];
  for (int i = 0; i < 8; i++) lenb[i] = uint8_t(bitlen >> (56 - 8 * i));
  update(c, lenb, 8);
  for (int i = 0; i < 8; i++) {
    out[4 * i] = uint8_t(c->h[i] >> 24);
    out[4 * i + 1] = uint8_t(c->h[i] >> 16);
    out[4 * i + 2] = uint8_t(c->h[i] >> 8);
    out[4 * i + 3] = uint8_t(c->h[i]);
  }
}

static void digest(const uint8_t *data, size_t n, uint8_t out[32]) {
  Ctx c;
  init(&c);
  update(&c, data, n);
  final(&c, out);
}

}  // namespace sha256

// --------------------------------------------------------------------------
// SHA-512 (FIPS 180-4) + reduction mod the ed25519 group order L — the
// host half of the batch challenge k = SHA512(R||A||M) mod L
// (crypto/ed25519/ed25519.go verification; ops/pallas_verify.py
// prepare_compact). One C call replaces a per-signature Python loop that
// measured ~50% of end-to-end batch time on a loaded host.

namespace sha512 {

static const uint64_t K[80] = {
    0x428a2f98d728ae22ULL, 0x7137449123ef65cdULL, 0xb5c0fbcfec4d3b2fULL,
    0xe9b5dba58189dbbcULL, 0x3956c25bf348b538ULL, 0x59f111f1b605d019ULL,
    0x923f82a4af194f9bULL, 0xab1c5ed5da6d8118ULL, 0xd807aa98a3030242ULL,
    0x12835b0145706fbeULL, 0x243185be4ee4b28cULL, 0x550c7dc3d5ffb4e2ULL,
    0x72be5d74f27b896fULL, 0x80deb1fe3b1696b1ULL, 0x9bdc06a725c71235ULL,
    0xc19bf174cf692694ULL, 0xe49b69c19ef14ad2ULL, 0xefbe4786384f25e3ULL,
    0x0fc19dc68b8cd5b5ULL, 0x240ca1cc77ac9c65ULL, 0x2de92c6f592b0275ULL,
    0x4a7484aa6ea6e483ULL, 0x5cb0a9dcbd41fbd4ULL, 0x76f988da831153b5ULL,
    0x983e5152ee66dfabULL, 0xa831c66d2db43210ULL, 0xb00327c898fb213fULL,
    0xbf597fc7beef0ee4ULL, 0xc6e00bf33da88fc2ULL, 0xd5a79147930aa725ULL,
    0x06ca6351e003826fULL, 0x142929670a0e6e70ULL, 0x27b70a8546d22ffcULL,
    0x2e1b21385c26c926ULL, 0x4d2c6dfc5ac42aedULL, 0x53380d139d95b3dfULL,
    0x650a73548baf63deULL, 0x766a0abb3c77b2a8ULL, 0x81c2c92e47edaee6ULL,
    0x92722c851482353bULL, 0xa2bfe8a14cf10364ULL, 0xa81a664bbc423001ULL,
    0xc24b8b70d0f89791ULL, 0xc76c51a30654be30ULL, 0xd192e819d6ef5218ULL,
    0xd69906245565a910ULL, 0xf40e35855771202aULL, 0x106aa07032bbd1b8ULL,
    0x19a4c116b8d2d0c8ULL, 0x1e376c085141ab53ULL, 0x2748774cdf8eeb99ULL,
    0x34b0bcb5e19b48a8ULL, 0x391c0cb3c5c95a63ULL, 0x4ed8aa4ae3418acbULL,
    0x5b9cca4f7763e373ULL, 0x682e6ff3d6b2b8a3ULL, 0x748f82ee5defb2fcULL,
    0x78a5636f43172f60ULL, 0x84c87814a1f0ab72ULL, 0x8cc702081a6439ecULL,
    0x90befffa23631e28ULL, 0xa4506cebde82bde9ULL, 0xbef9a3f7b2c67915ULL,
    0xc67178f2e372532bULL, 0xca273eceea26619cULL, 0xd186b8c721c0c207ULL,
    0xeada7dd6cde0eb1eULL, 0xf57d4f7fee6ed178ULL, 0x06f067aa72176fbaULL,
    0x0a637dc5a2c898a6ULL, 0x113f9804bef90daeULL, 0x1b710b35131c471bULL,
    0x28db77f523047d84ULL, 0x32caab7b40c72493ULL, 0x3c9ebe0a15c9bebcULL,
    0x431d67c49c100d4cULL, 0x4cc5d4becb3e42b6ULL, 0x597f299cfc657e2aULL,
    0x5fcb6fab3ad6faecULL, 0x6c44198c4a475817ULL};

static inline uint64_t rotr64(uint64_t x, int n) {
  return (x >> n) | (x << (64 - n));
}

struct Ctx {
  uint64_t h[8];
  uint8_t buf[128];
  size_t buflen;
  uint64_t total;  // bytes
};

static void init(Ctx *c) {
  static const uint64_t H0[8] = {
      0x6a09e667f3bcc908ULL, 0xbb67ae8584caa73bULL, 0x3c6ef372fe94f82bULL,
      0xa54ff53a5f1d36f1ULL, 0x510e527fade682d1ULL, 0x9b05688c2b3e6c1fULL,
      0x1f83d9abfb41bd6bULL, 0x5be0cd19137e2179ULL};
  memcpy(c->h, H0, sizeof H0);
  c->buflen = 0;
  c->total = 0;
}

static void compress(Ctx *c, const uint8_t *p) {
  uint64_t w[80];
  for (int i = 0; i < 16; i++) {
    w[i] = 0;
    for (int b = 0; b < 8; b++) w[i] = (w[i] << 8) | p[8 * i + b];
  }
  for (int i = 16; i < 80; i++) {
    uint64_t s0 = rotr64(w[i - 15], 1) ^ rotr64(w[i - 15], 8) ^ (w[i - 15] >> 7);
    uint64_t s1 = rotr64(w[i - 2], 19) ^ rotr64(w[i - 2], 61) ^ (w[i - 2] >> 6);
    w[i] = w[i - 16] + s0 + w[i - 7] + s1;
  }
  uint64_t a = c->h[0], b = c->h[1], cc = c->h[2], d = c->h[3], e = c->h[4],
           f = c->h[5], g = c->h[6], h = c->h[7];
  for (int i = 0; i < 80; i++) {
    uint64_t S1 = rotr64(e, 14) ^ rotr64(e, 18) ^ rotr64(e, 41);
    uint64_t ch = (e & f) ^ (~e & g);
    uint64_t t1 = h + S1 + ch + K[i] + w[i];
    uint64_t S0 = rotr64(a, 28) ^ rotr64(a, 34) ^ rotr64(a, 39);
    uint64_t maj = (a & b) ^ (a & cc) ^ (b & cc);
    uint64_t t2 = S0 + maj;
    h = g; g = f; f = e; e = d + t1;
    d = cc; cc = b; b = a; a = t1 + t2;
  }
  c->h[0] += a; c->h[1] += b; c->h[2] += cc; c->h[3] += d;
  c->h[4] += e; c->h[5] += f; c->h[6] += g; c->h[7] += h;
}

static void update(Ctx *c, const uint8_t *data, size_t n) {
  c->total += n;
  if (c->buflen) {
    size_t take = 128 - c->buflen;
    if (take > n) take = n;
    memcpy(c->buf + c->buflen, data, take);
    c->buflen += take;
    data += take;
    n -= take;
    if (c->buflen == 128) {
      compress(c, c->buf);
      c->buflen = 0;
    }
  }
  while (n >= 128) {
    compress(c, data);
    data += 128;
    n -= 128;
  }
  if (n) {
    memcpy(c->buf, data, n);
    c->buflen = n;
  }
}

static void final(Ctx *c, uint8_t out[64]) {
  uint64_t bits = c->total * 8;
  uint8_t pad = 0x80;
  update(c, &pad, 1);
  uint8_t z = 0;
  while (c->buflen != 112) update(c, &z, 1);
  uint8_t len[16] = {0};
  for (int i = 0; i < 8; i++) len[15 - i] = uint8_t(bits >> (8 * i));
  // counter only tracks real input; neutralize padding's contribution
  c->total = 0;
  update(c, len, 16);
  for (int i = 0; i < 8; i++)
    for (int b = 0; b < 8; b++) out[8 * i + b] = uint8_t(c->h[i] >> (56 - 8 * b));
}

// k = digest (64B little-endian integer) mod L, L = 2^252 + C,
// C = 27742317777372353535851937790883648493. Since 2^252 ≡ -C (mod L),
// each fold rewrites x = hi*2^252 + lo as lo + K_r - hi*C where K_r is a
// precomputed multiple of L large enough to keep the result positive
// (K1 = L<<133, K2 = L<<7, K3 = L; sizes 512 -> 386 -> 260 -> 254 bits),
// then conditionally subtracts L (at most 3 times; x3 < 2^254 < 4L).
static const uint64_t C_LO = 0x5812631a5cf5d3edULL;
static const uint64_t C_HI = 0x14def9dea2f79cd6ULL;  // C = C_HI<<64 | C_LO
static const uint64_t L_LIMBS[4] = {C_LO, C_HI, 0, 0x1000000000000000ULL};
static const uint64_t FOLD_K[3][7] = {
    {0x0000000000000000ULL, 0x0000000000000000ULL, 0x024c634b9eba7da0ULL,
     0x9bdf3bd45ef39acbULL, 0x0000000000000002ULL, 0x0000000000000000ULL,
     0x0000000000000002ULL},
    {0x09318d2e7ae9f680ULL, 0x6f7cef517bce6b2cULL, 0x000000000000000aULL,
     0x0000000000000000ULL, 0x0000000000000008ULL, 0x0000000000000000ULL,
     0x0000000000000000ULL},
    {0x5812631a5cf5d3edULL, 0x14def9dea2f79cd6ULL, 0x0000000000000000ULL,
     0x1000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL,
     0x0000000000000000ULL}};

static void mod_l(const uint8_t digest[64], uint8_t out[32]) {
  // x: 8 limbs LE; every intermediate fits in 7 limbs after round 1
  uint64_t x[8] = {0};
  for (int i = 0; i < 8; i++)
    for (int b = 0; b < 8; b++) x[i] |= uint64_t(digest[8 * i + b]) << (8 * b);
  for (int round = 0; round < 3; round++) {
    // hi = x >> 252 (up to 5 limbs), lo = x & (2^252 - 1)
    uint64_t hi[5];
    for (int i = 0; i < 5; i++) {
      uint64_t v = (i + 3 < 8) ? (x[i + 3] >> 60) : 0;
      if (i + 4 < 8) v |= x[i + 4] << 4;
      hi[i] = v;
    }
    uint64_t lo[4] = {x[0], x[1], x[2], x[3] & 0x0fffffffffffffffULL};
    // t = hi * C (7 limbs)
    uint64_t t[7];
    unsigned __int128 carry = 0;
    for (int i = 0; i < 7; i++) {
      unsigned __int128 acc = carry;
      if (i < 5) acc += (unsigned __int128)hi[i] * C_LO;
      if (i >= 1 && i <= 5) acc += (unsigned __int128)hi[i - 1] * C_HI;
      t[i] = uint64_t(acc);
      carry = acc >> 64;
    }
    // x = lo + K_round - t  (guaranteed non-negative)
    memset(x, 0, sizeof x);
    unsigned __int128 acc2 = 0;
    uint64_t borrow = 0;
    for (int i = 0; i < 7; i++) {
      acc2 += (i < 4 ? lo[i] : 0);
      acc2 += FOLD_K[round][i];
      uint64_t add = uint64_t(acc2);
      unsigned __int128 d = (unsigned __int128)add - t[i] - borrow;
      x[i] = uint64_t(d);
      borrow = (uint64_t)(d >> 64) ? 1 : 0;
      acc2 >>= 64;
    }
  }
  // now x < 2^254 < 4L: subtract L while x >= L
  for (int rep = 0; rep < 3; rep++) {
    bool ge = true;
    for (int i = 3; i >= 0; i--) {
      if (x[i] > L_LIMBS[i]) break;
      if (x[i] < L_LIMBS[i]) { ge = false; break; }
    }
    if (!ge) break;
    uint64_t borrow = 0;
    for (int i = 0; i < 4; i++) {
      unsigned __int128 d = (unsigned __int128)x[i] - L_LIMBS[i] - borrow;
      x[i] = uint64_t(d);
      borrow = (uint64_t)(d >> 64) ? 1 : 0;
    }
  }
  for (int i = 0; i < 4; i++)
    for (int b = 0; b < 8; b++) out[8 * i + b] = uint8_t(x[i] >> (8 * b));
}

}  // namespace sha512

// --------------------------------------------------------------------------
// RFC-6962 merkle (crypto/merkle/tree.go semantics)

static void leaf_hash(const uint8_t *data, size_t n, uint8_t out[32]) {
  sha256::Ctx c;
  sha256::init(&c);
  uint8_t prefix = 0x00;
  sha256::update(&c, &prefix, 1);
  sha256::update(&c, data, n);
  sha256::final(&c, out);
}

static void inner_hash(const uint8_t *l, const uint8_t *r, uint8_t out[32]) {
  sha256::Ctx c;
  sha256::init(&c);
  uint8_t prefix = 0x01;
  sha256::update(&c, &prefix, 1);
  sha256::update(&c, l, 32);
  sha256::update(&c, r, 32);
  sha256::final(&c, out);
}

static size_t split_point(size_t n) {
  size_t k = 1;
  while (k * 2 < n) k *= 2;
  return k;
}

static void merkle_root_hashes(std::vector<uint8_t> &hashes, size_t lo,
                               size_t hi, uint8_t out[32]) {
  size_t n = hi - lo;
  if (n == 1) {
    memcpy(out, &hashes[32 * lo], 32);
    return;
  }
  size_t k = split_point(n);
  uint8_t left[32], right[32];
  merkle_root_hashes(hashes, lo, lo + k, left);
  merkle_root_hashes(hashes, lo + k, hi, right);
  inner_hash(left, right, out);
}

// merkle_root(items: list[bytes]) -> bytes
static PyObject *py_merkle_root(PyObject *, PyObject *args) {
  PyObject *items;
  if (!PyArg_ParseTuple(args, "O", &items)) return nullptr;
  PyObject *seq = PySequence_Fast(items, "expected a sequence");
  if (!seq) return nullptr;
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  uint8_t out[32];
  if (n == 0) {
    sha256::digest(nullptr, 0, out);
    Py_DECREF(seq);
    return PyBytes_FromStringAndSize((const char *)out, 32);
  }
  std::vector<uint8_t> hashes(size_t(n) * 32);
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject *item = PySequence_Fast_GET_ITEM(seq, i);
    char *buf;
    Py_ssize_t len;
    if (PyBytes_AsStringAndSize(item, &buf, &len) < 0) {
      Py_DECREF(seq);
      return nullptr;
    }
    leaf_hash((const uint8_t *)buf, size_t(len), &hashes[32 * size_t(i)]);
  }
  Py_DECREF(seq);
  merkle_root_hashes(hashes, 0, size_t(n), out);
  return PyBytes_FromStringAndSize((const char *)out, 32);
}

// sha256_many(items: list[bytes]) -> bytes (concatenated 32B digests)
static PyObject *py_sha256_many(PyObject *, PyObject *args) {
  PyObject *items;
  if (!PyArg_ParseTuple(args, "O", &items)) return nullptr;
  PyObject *seq = PySequence_Fast(items, "expected a sequence");
  if (!seq) return nullptr;
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  PyObject *out = PyBytes_FromStringAndSize(nullptr, n * 32);
  if (!out) {
    Py_DECREF(seq);
    return nullptr;
  }
  uint8_t *op = (uint8_t *)PyBytes_AS_STRING(out);
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject *item = PySequence_Fast_GET_ITEM(seq, i);
    char *buf;
    Py_ssize_t len;
    if (PyBytes_AsStringAndSize(item, &buf, &len) < 0) {
      Py_DECREF(seq);
      Py_DECREF(out);
      return nullptr;
    }
    sha256::digest((const uint8_t *)buf, size_t(len), op + 32 * i);
  }
  Py_DECREF(seq);
  return out;
}

// pack_le_limbs(encodings: bytes (n*32), n: int) -> bytes (n*20 int32 LE)
// Low 255 bits of each 32-byte little-endian encoding into 20 radix-2^13
// limbs — the fe.py input format (ops/backend.py _pack_le_limbs).
static PyObject *py_pack_le_limbs(PyObject *, PyObject *args) {
  Py_buffer view;
  Py_ssize_t n;
  if (!PyArg_ParseTuple(args, "y*n", &view, &n)) return nullptr;
  if (view.len < n * 32) {
    PyBuffer_Release(&view);
    PyErr_SetString(PyExc_ValueError, "buffer too small");
    return nullptr;
  }
  PyObject *out = PyBytes_FromStringAndSize(nullptr, n * 20 * 4);
  if (!out) {
    PyBuffer_Release(&view);
    return nullptr;
  }
  int32_t *op = (int32_t *)PyBytes_AS_STRING(out);
  const uint8_t *ip = (const uint8_t *)view.buf;
  for (Py_ssize_t i = 0; i < n; i++) {
    const uint8_t *enc = ip + 32 * i;
    // 255-bit value as four 64-bit words (top bit cleared)
    uint64_t w[4];
    for (int j = 0; j < 4; j++) {
      w[j] = 0;
      for (int b = 0; b < 8; b++) w[j] |= uint64_t(enc[8 * j + b]) << (8 * b);
    }
    w[3] &= 0x7fffffffffffffffULL;
    for (int limb = 0; limb < 20; limb++) {
      int bit = limb * 13;
      int word = bit >> 6, off = bit & 63;
      uint64_t v = w[word] >> off;
      if (off > 64 - 13 && word < 3) v |= w[word + 1] << (64 - off);
      op[20 * i + limb] = int32_t(v & 0x1fff);
    }
  }
  PyBuffer_Release(&view);
  return out;
}

// pack_bits_le(scalars: bytes (n*32), n: int, nbits: int)
//   -> bytes (nbits * n int32 LE), transposed for the ladder.
static PyObject *py_pack_bits_le(PyObject *, PyObject *args) {
  Py_buffer view;
  Py_ssize_t n;
  int nbits;
  if (!PyArg_ParseTuple(args, "y*ni", &view, &n, &nbits)) return nullptr;
  if (view.len < n * 32 || nbits > 256) {
    PyBuffer_Release(&view);
    PyErr_SetString(PyExc_ValueError, "bad buffer/nbits");
    return nullptr;
  }
  PyObject *out = PyBytes_FromStringAndSize(nullptr, (Py_ssize_t)nbits * n * 4);
  if (!out) {
    PyBuffer_Release(&view);
    return nullptr;
  }
  int32_t *op = (int32_t *)PyBytes_AS_STRING(out);
  const uint8_t *ip = (const uint8_t *)view.buf;
  for (Py_ssize_t i = 0; i < n; i++) {
    const uint8_t *s = ip + 32 * i;
    for (int b = 0; b < nbits; b++) {
      op[(Py_ssize_t)b * n + i] = (s[b >> 3] >> (b & 7)) & 1;
    }
  }
  PyBuffer_Release(&view);
  return out;
}


// --------------------------------------------------------------------------
// Merlin transcripts on STROBE-128 / Keccak-f[1600] — the sr25519
// (schnorrkel) challenge computation, which dominates host-side cost of
// the device sr25519 lane (pure-Python merlin is ~3 ms/signature; this is
// ~2 us). Mirrors crypto/_merlin.py bit-for-bit (differentially tested).

namespace merlin {

static const uint64_t RC[24] = {
    0x0000000000000001ULL, 0x0000000000008082ULL, 0x800000000000808aULL,
    0x8000000080008000ULL, 0x000000000000808bULL, 0x0000000080000001ULL,
    0x8000000080008081ULL, 0x8000000000008009ULL, 0x000000000000008aULL,
    0x0000000000000088ULL, 0x0000000080008009ULL, 0x000000008000000aULL,
    0x000000008000808bULL, 0x800000000000008bULL, 0x8000000000008089ULL,
    0x8000000000008003ULL, 0x8000000000008002ULL, 0x8000000000000080ULL,
    0x000000000000800aULL, 0x800000008000000aULL, 0x8000000080008081ULL,
    0x8000000000008080ULL, 0x0000000080000001ULL, 0x8000000080008008ULL};

static inline uint64_t rotl64(uint64_t v, int n) {
  return n ? (v << n) | (v >> (64 - n)) : v;
}

static const int ROTC[5][5] = {{0, 36, 3, 41, 18},
                               {1, 44, 10, 45, 2},
                               {62, 6, 43, 15, 61},
                               {28, 55, 25, 21, 56},
                               {27, 20, 39, 8, 14}};

static void keccak_f1600(uint8_t state[200]) {
  uint64_t lanes[5][5];
  for (int x = 0; x < 5; x++)
    for (int y = 0; y < 5; y++)
      memcpy(&lanes[x][y], state + 8 * (x + 5 * y), 8);
  for (int r = 0; r < 24; r++) {
    uint64_t c[5], d[5];
    for (int x = 0; x < 5; x++)
      c[x] = lanes[x][0] ^ lanes[x][1] ^ lanes[x][2] ^ lanes[x][3] ^ lanes[x][4];
    for (int x = 0; x < 5; x++)
      d[x] = c[(x + 4) % 5] ^ rotl64(c[(x + 1) % 5], 1);
    for (int x = 0; x < 5; x++)
      for (int y = 0; y < 5; y++) lanes[x][y] ^= d[x];
    uint64_t b[5][5];
    for (int x = 0; x < 5; x++)
      for (int y = 0; y < 5; y++)
        b[y][(2 * x + 3 * y) % 5] = rotl64(lanes[x][y], ROTC[x][y]);
    for (int x = 0; x < 5; x++)
      for (int y = 0; y < 5; y++)
        lanes[x][y] = b[x][y] ^ ((~b[(x + 1) % 5][y]) & b[(x + 2) % 5][y]);
    lanes[0][0] ^= RC[r];
  }
  for (int x = 0; x < 5; x++)
    for (int y = 0; y < 5; y++)
      memcpy(state + 8 * (x + 5 * y), &lanes[x][y], 8);
}

static const int STROBE_R = 166;
static const uint8_t F_I = 1, F_A = 1 << 1, F_C = 1 << 2, F_M = 1 << 4,
                     F_K = 1 << 5;

struct Strobe {
  uint8_t state[200];
  int pos, pos_begin;

  void run_f() {
    state[pos] ^= (uint8_t)pos_begin;
    state[pos + 1] ^= 0x04;
    state[STROBE_R + 1] ^= 0x80;
    keccak_f1600(state);
    pos = 0;
    pos_begin = 0;
  }

  void absorb(const uint8_t *d, size_t n) {
    for (size_t i = 0; i < n; i++) {
      state[pos] ^= d[i];
      if (++pos == STROBE_R) run_f();
    }
  }

  void squeeze(uint8_t *out, size_t n) {
    for (size_t i = 0; i < n; i++) {
      out[i] = state[pos];
      state[pos] = 0;
      if (++pos == STROBE_R) run_f();
    }
  }

  void begin_op(uint8_t flags) {
    uint8_t old_begin = (uint8_t)pos_begin;
    pos_begin = pos + 1;
    uint8_t hdr[2] = {old_begin, flags};
    absorb(hdr, 2);
    if ((flags & (F_C | F_K)) && pos != 0) run_f();
  }

  void meta_ad(const uint8_t *d, size_t n, bool more) {
    if (!more) begin_op(F_M | F_A);
    absorb(d, n);
  }

  void ad(const uint8_t *d, size_t n) {
    begin_op(F_A);
    absorb(d, n);
  }

  void prf(uint8_t *out, size_t n) {
    begin_op(F_I | F_A | F_C);
    squeeze(out, n);
  }

  void init(const uint8_t *label, size_t n) {
    memset(state, 0, 200);
    const uint8_t hdr[6] = {1, STROBE_R + 2, 1, 0, 1, 12 * 8};
    memcpy(state, hdr, 6);
    memcpy(state + 6, "STROBEv1.0.2", 12);
    keccak_f1600(state);
    pos = 0;
    pos_begin = 0;
    meta_ad(label, n, false);
  }
};

static void append_message(Strobe &s, const uint8_t *label, size_t ln,
                           const uint8_t *msg, size_t mn) {
  uint8_t le[4] = {(uint8_t)(mn & 0xff), (uint8_t)((mn >> 8) & 0xff),
                   (uint8_t)((mn >> 16) & 0xff), (uint8_t)((mn >> 24) & 0xff)};
  s.meta_ad(label, ln, false);
  s.meta_ad(le, 4, true);
  s.ad(msg, mn);
}

}  // namespace merlin

// Shared schnorrkel signing-transcript framing (consensus-critical label
// sequence) -> the 64-byte "sign:c" challenge. Used by both the
// challenge-only (sr25519_challenges_buf) and full-verify lanes so the
// framing cannot diverge.
static void sr25519_challenge_64(const uint8_t *ctx, size_t ctx_len,
                                 const uint8_t *msg, size_t msg_len,
                                 const uint8_t *pub, const uint8_t *r,
                                 uint8_t out[64]) {
  merlin::Strobe s;
  s.init((const uint8_t *)"Merlin v1.0", 11);
  merlin::append_message(s, (const uint8_t *)"dom-sep", 7,
                         (const uint8_t *)"SigningContext", 14);
  merlin::append_message(s, (const uint8_t *)"", 0, ctx, ctx_len);
  merlin::append_message(s, (const uint8_t *)"sign-bytes", 10, msg, msg_len);
  merlin::append_message(s, (const uint8_t *)"proto-name", 10,
                         (const uint8_t *)"Schnorr-sig", 11);
  merlin::append_message(s, (const uint8_t *)"sign:pk", 7, pub, 32);
  merlin::append_message(s, (const uint8_t *)"sign:R", 6, r, 32);
  uint8_t le[4] = {64, 0, 0, 0};
  s.meta_ad((const uint8_t *)"sign:c", 6, false);
  s.meta_ad(le, 4, true);
  s.prf(out, 64);
}

// --------------------------------------------------------------------------
// GF(2^255-19) + edwards25519 + ristretto255 — the native sr25519
// verification lane (crypto/sr25519/: schnorrkel R == [s]B - [k]A). The
// pure-Python crypto/_ristretto.py is the differential oracle; formulas
// mirror crypto/_edwards.py (add-2008-hwcd-3 / dbl-2008-hwcd, a=-1).

namespace ed {

typedef uint64_t fe[5];  // radix-2^51
static const uint64_t MASK51 = 0x7ffffffffffffULL;

static const fe D_FE = {0x34dca135978a3ULL, 0x1a8283b156ebdULL, 0x5e7a26001c029ULL, 0x739c663a03cbbULL, 0x52036cee2b6ffULL};
static const fe D2_FE = {0x69b9426b2f159ULL, 0x35050762add7aULL, 0x3cf44c0038052ULL, 0x6738cc7407977ULL, 0x2406d9dc56dffULL};
static const fe SQRT_M1_FE = {0x61b274a0ea0b0ULL, 0xd5a5fc8f189dULL, 0x7ef5e9cbd0c60ULL, 0x78595a6804c9eULL, 0x2b8324804fc1dULL};
static const fe BASE_X_FE = {0x62d608f25d51aULL, 0x412a4b4f6592aULL, 0x75b7171a4b31dULL, 0x1ff60527118feULL, 0x216936d3cd6e5ULL};
static const fe BASE_Y_FE = {0x6666666666658ULL, 0x4ccccccccccccULL, 0x1999999999999ULL, 0x3333333333333ULL, 0x6666666666666ULL};
static const fe BASE_T_FE = {0x68ab3a5b7dda3ULL, 0xeea2a5eadbbULL, 0x2af8df483c27eULL, 0x332b375274732ULL, 0x67875f0fd78b7ULL};
static const uint8_t POW_P58_BYTES[32] = {
    0xfd, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff,
    0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff,
    0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0x0f};

static void fe_copy(fe h, const fe a) { memcpy(h, a, sizeof(fe)); }
static void fe_zero(fe h) { memset(h, 0, sizeof(fe)); }
static void fe_one(fe h) { fe_zero(h); h[0] = 1; }

static void fe_add(fe h, const fe a, const fe b) {
  for (int i = 0; i < 5; i++) h[i] = a[i] + b[i];
}

// h = a - b; adds 2p per limb to stay positive (inputs < 2^52)
static void fe_sub(fe h, const fe a, const fe b) {
  static const uint64_t TWO_P[5] = {0xfffffffffffdaULL, 0xffffffffffffeULL,
                                    0xffffffffffffeULL, 0xffffffffffffeULL,
                                    0xffffffffffffeULL};
  for (int i = 0; i < 5; i++) h[i] = a[i] + TWO_P[i] - b[i];
}

// carry-propagate so every limb < 2^51 (values stay mod p)
static void fe_carry(fe h) {
  uint64_t c;
  for (int r = 0; r < 2; r++) {
    c = h[0] >> 51; h[0] &= MASK51; h[1] += c;
    c = h[1] >> 51; h[1] &= MASK51; h[2] += c;
    c = h[2] >> 51; h[2] &= MASK51; h[3] += c;
    c = h[3] >> 51; h[3] &= MASK51; h[4] += c;
    c = h[4] >> 51; h[4] &= MASK51; h[0] += c * 19;
  }
}

static void fe_mul(fe h, const fe a, const fe b) {
  unsigned __int128 t[5] = {0, 0, 0, 0, 0};
  for (int i = 0; i < 5; i++) {
    for (int j = 0; j < 5; j++) {
      int k = i + j;
      unsigned __int128 prod = (unsigned __int128)a[i] * b[j];
      if (k >= 5) {
        k -= 5;
        prod *= 19;
      }
      t[k] += prod;
    }
  }
  // carry chain (each t[i] < ~2^115, fits)
  uint64_t r[5];
  unsigned __int128 c = 0;
  for (int i = 0; i < 5; i++) {
    t[i] += c;
    r[i] = (uint64_t)(t[i] & MASK51);
    c = t[i] >> 51;
  }
  r[0] += (uint64_t)(c * 19);
  memcpy(h, r, sizeof r);
  fe_carry(h);
}

static void fe_sq(fe h, const fe a) { fe_mul(h, a, a); }

// canonical little-endian bytes (full reduction)
static void fe_tobytes(uint8_t out[32], const fe a) {
  fe t;
  fe_copy(t, a);
  fe_carry(t);
  // final conditional subtract p (possibly twice)
  for (int rep = 0; rep < 2; rep++) {
    uint64_t borrow_p[5] = {0x7ffffffffffedULL, MASK51, MASK51, MASK51, MASK51};
    bool ge = true;
    for (int i = 4; i >= 0; i--) {
      if (t[i] > borrow_p[i]) break;
      if (t[i] < borrow_p[i]) { ge = false; break; }
    }
    if (!ge) break;
    uint64_t borrow = 0;
    for (int i = 0; i < 5; i++) {
      uint64_t d = t[i] - borrow_p[i] - borrow;
      borrow = (t[i] < borrow_p[i] + borrow) ? 1 : 0;
      t[i] = d & MASK51;
    }
  }
  uint64_t w0 = t[0] | (t[1] << 51);
  uint64_t w1 = (t[1] >> 13) | (t[2] << 38);
  uint64_t w2 = (t[2] >> 26) | (t[3] << 25);
  uint64_t w3 = (t[3] >> 39) | (t[4] << 12);
  uint64_t ws[4] = {w0, w1, w2, w3};
  for (int i = 0; i < 4; i++)
    for (int b = 0; b < 8; b++) out[8 * i + b] = (uint8_t)(ws[i] >> (8 * b));
}

static void fe_frombytes(fe h, const uint8_t in[32]) {
  uint64_t w[4];
  for (int i = 0; i < 4; i++) {
    w[i] = 0;
    for (int b = 0; b < 8; b++) w[i] |= (uint64_t)in[8 * i + b] << (8 * b);
  }
  h[0] = w[0] & MASK51;
  h[1] = ((w[0] >> 51) | (w[1] << 13)) & MASK51;
  h[2] = ((w[1] >> 38) | (w[2] << 26)) & MASK51;
  h[3] = ((w[2] >> 25) | (w[3] << 39)) & MASK51;
  h[4] = (w[3] >> 12) & MASK51;  // drops bit 255
}

static bool fe_is_negative(const fe a) {
  uint8_t b[32];
  fe_tobytes(b, a);
  return b[0] & 1;
}

static bool fe_is_zero(const fe a) {
  uint8_t b[32];
  fe_tobytes(b, a);
  for (int i = 0; i < 32; i++)
    if (b[i]) return false;
  return true;
}

static bool fe_eq(const fe a, const fe b) {
  fe d;
  fe_sub(d, a, b);
  return fe_is_zero(d);
}

static void fe_neg(fe h, const fe a) {
  fe z;
  fe_zero(z);
  fe_sub(h, z, a);
  fe_carry(h);
}

// a^((p-5)/8) by square-and-multiply over the constant exponent
static void fe_pow_p58(fe h, const fe a) {
  fe result, base;
  fe_one(result);
  fe_copy(base, a);
  for (int bit = 0; bit < 252; bit++) {
    if ((POW_P58_BYTES[bit >> 3] >> (bit & 7)) & 1) fe_mul(result, result, base);
    if (bit != 251) fe_sq(base, base);
  }
  fe_copy(h, result);
}

// _edwards._sqrt_ratio: r with v*r^2 == u, or false (r undefined)
static bool fe_sqrt_ratio(fe r, const fe u, const fe v) {
  fe v3, v7, t, uv7, pw;
  fe_sq(v3, v);
  fe_mul(v3, v3, v);       // v^3
  fe_sq(v7, v3);
  fe_mul(v7, v7, v);       // v^7
  fe_mul(uv7, u, v7);
  fe_pow_p58(pw, uv7);     // (u v^7)^((p-5)/8)
  fe_mul(t, u, v3);
  fe_mul(r, t, pw);        // u v^3 (u v^7)^((p-5)/8)
  fe check;
  fe_sq(check, r);
  fe_mul(check, check, v);  // v r^2
  if (fe_eq(check, u)) return true;
  fe nu;
  fe_neg(nu, u);
  if (fe_eq(check, nu)) {
    fe_mul(r, r, SQRT_M1_FE);
    return true;
  }
  return false;
}

// _ristretto._invsqrt: (was_square, 1/sqrt(u)); u=0 -> (true, 0)
static bool fe_invsqrt(fe r, const fe u) {
  if (fe_is_zero(u)) {
    fe_zero(r);
    return true;
  }
  fe one;
  fe_one(one);
  if (fe_sqrt_ratio(r, one, u)) return true;
  // not a square: r = sqrt(i/u) (decode rejects via ok=false anyway)
  fe_sqrt_ratio(r, SQRT_M1_FE, u);
  return false;
}

struct point {
  fe x, y, z, t;
};

static void pt_identity(point &p) {
  fe_zero(p.x);
  fe_one(p.y);
  fe_one(p.z);
  fe_zero(p.t);
}

// add-2008-hwcd-3, a=-1 (crypto/_edwards.py point_add)
static void pt_add(point &h, const point &p, const point &q) {
  fe a, b, c, d, e, f, g, hh, t1, t2;
  fe_sub(t1, p.y, p.x);
  fe_sub(t2, q.y, q.x);
  fe_carry(t1);
  fe_carry(t2);
  fe_mul(a, t1, t2);
  fe_add(t1, p.y, p.x);
  fe_add(t2, q.y, q.x);
  fe_carry(t1);
  fe_carry(t2);
  fe_mul(b, t1, t2);
  fe_mul(c, p.t, D2_FE);
  fe_mul(c, c, q.t);
  fe_mul(d, p.z, q.z);
  fe_add(d, d, d);
  fe_carry(d);
  fe_sub(e, b, a);
  fe_sub(f, d, c);
  fe_add(g, d, c);
  fe_add(hh, b, a);
  fe_carry(e);
  fe_carry(f);
  fe_carry(g);
  fe_carry(hh);
  fe_mul(h.x, e, f);
  fe_mul(h.y, g, hh);
  fe_mul(h.z, f, g);
  fe_mul(h.t, e, hh);
}

// dbl-2008-hwcd, a=-1 (crypto/_edwards.py point_double)
static void pt_double(point &h, const point &p) {
  fe a, b, c, d, e, f, g, hh, t1;
  fe_sq(a, p.x);
  fe_sq(b, p.y);
  fe_sq(c, p.z);
  fe_add(c, c, c);
  fe_carry(c);
  fe_neg(d, a);
  fe_add(t1, p.x, p.y);
  fe_carry(t1);
  fe_sq(e, t1);
  fe_sub(e, e, a);
  fe_sub(e, e, b);
  fe_carry(e);
  fe_add(g, d, b);
  fe_carry(g);
  fe_sub(f, g, c);
  fe_carry(f);
  fe_sub(hh, d, b);
  fe_carry(hh);
  fe_mul(h.x, e, f);
  fe_mul(h.y, g, hh);
  fe_mul(h.z, f, g);
  fe_mul(h.t, e, hh);
}

static void pt_neg(point &h, const point &p) {
  fe_neg(h.x, p.x);
  fe_copy(h.y, p.y);
  fe_copy(h.z, p.z);
  fe_neg(h.t, p.t);
}

// 4-bit fixed-window scalar multiply: scalar is 32 LE bytes (< L)
static void pt_scalar_mul(point &h, const uint8_t scalar[32], const point &p) {
  point table[16];
  pt_identity(table[0]);
  table[1] = p;
  for (int i = 2; i < 16; i++) pt_add(table[i], table[i - 1], p);
  pt_identity(h);
  bool started = false;
  for (int i = 63; i >= 0; i--) {
    int nib = (scalar[i >> 1] >> ((i & 1) ? 4 : 0)) & 0xf;
    if (started) {
      pt_double(h, h);
      pt_double(h, h);
      pt_double(h, h);
      pt_double(h, h);
    }
    if (nib) {
      if (started) {
        pt_add(h, h, table[nib]);
      } else {
        h = table[nib];
        started = true;
      }
    } else if (started) {
      // nothing to add
    }
  }
}

// ristretto255 DECODE (crypto/_ristretto.py decode); false on reject
static bool ristretto_decode(point &out, const uint8_t in[32]) {
  // reject s >= p or negative (odd)
  static const uint8_t P_BYTES[32] = {
      0xed, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff,
      0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff,
      0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0xff, 0x7f};
  bool lt = false;
  for (int i = 31; i >= 0; i--) {
    if (in[i] < P_BYTES[i]) { lt = true; break; }
    if (in[i] > P_BYTES[i]) return false;
  }
  if (!lt) return false;          // s == p
  if (in[0] & 1) return false;    // negative
  fe s, ss, u1, u2, u2s, v, t1, t2, one;
  fe_frombytes(s, in);
  fe_one(one);
  fe_sq(ss, s);
  fe_sub(u1, one, ss);
  fe_carry(u1);
  fe_add(u2, one, ss);
  fe_carry(u2);
  fe_sq(u2s, u2);
  fe_mul(t1, D_FE, u1);
  fe_mul(t1, t1, u1);
  fe_neg(t1, t1);
  fe_sub(v, t1, u2s);
  fe_carry(v);
  fe invsq, vu2s;
  fe_mul(vu2s, v, u2s);
  bool ok = fe_invsqrt(invsq, vu2s);
  fe den_x, den_y, x, y, t;
  fe_mul(den_x, invsq, u2);
  fe_mul(den_y, invsq, den_x);
  fe_mul(den_y, den_y, v);
  fe_add(t1, s, s);
  fe_carry(t1);
  fe_mul(x, t1, den_x);
  if (fe_is_negative(x)) fe_neg(x, x);
  fe_mul(y, u1, den_y);
  fe_mul(t, x, y);
  if (!ok || fe_is_negative(t) || fe_is_zero(y)) return false;
  fe_copy(out.x, x);
  fe_copy(out.y, y);
  fe_one(out.z);
  fe_copy(out.t, t);
  return true;
}

// ristretto equality: x1 y2 == y1 x2 or y1 y2 == x1 x2
static bool ristretto_eq(const point &a, const point &b) {
  fe l, r;
  fe_mul(l, a.x, b.y);
  fe_mul(r, a.y, b.x);
  if (fe_eq(l, r)) return true;
  fe_mul(l, a.y, b.y);
  fe_mul(r, a.x, b.x);
  return fe_eq(l, r);
}

}  // namespace ed

// OpenSSL's asm SHA-512 when libcrypto is present (no dev headers in the
// image, so resolve the one-shot SHA512() via dlopen; the scalar
// implementation above is the fallback and the differential-test oracle).
#include <dlfcn.h>
typedef unsigned char *(*ossl_sha512_fn)(const unsigned char *, size_t,
                                         unsigned char *);
static ossl_sha512_fn ossl_sha512() {
  static ossl_sha512_fn fn = []() -> ossl_sha512_fn {
    void *h = dlopen("libcrypto.so.3", RTLD_NOW | RTLD_LOCAL);
    if (!h) h = dlopen("libcrypto.so", RTLD_NOW | RTLD_LOCAL);
    if (!h) return nullptr;
    return (ossl_sha512_fn)dlsym(h, "SHA512");
  }();
  return fn;
}

// ed25519_challenges(rs: n*32 bytes, pubs: n*32 bytes, msgs: seq[bytes])
//   -> bytes (n*32): k_i = SHA512(R_i || A_i || M_i) mod L, little-endian.
static PyObject *py_ed25519_challenges(PyObject *, PyObject *args) {
  Py_buffer rs, pubs;
  PyObject *msgs;
  int no_ossl = 0;  // tests force the scalar fallback path
  if (!PyArg_ParseTuple(args, "y*y*O|p", &rs, &pubs, &msgs, &no_ossl))
    return nullptr;
  PyObject *seq = PySequence_Fast(msgs, "expected a sequence of messages");
  if (!seq) {
    PyBuffer_Release(&rs);
    PyBuffer_Release(&pubs);
    return nullptr;
  }
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  if (rs.len < 32 * n || pubs.len < 32 * n) {
    Py_DECREF(seq);
    PyBuffer_Release(&rs);
    PyBuffer_Release(&pubs);
    PyErr_SetString(PyExc_ValueError, "rs/pubs must be at least n*32 bytes");
    return nullptr;
  }
  PyObject *out = PyBytes_FromStringAndSize(nullptr, n * 32);
  if (!out) {
    Py_DECREF(seq);
    PyBuffer_Release(&rs);
    PyBuffer_Release(&pubs);
    return nullptr;
  }
  uint8_t *dst = (uint8_t *)PyBytes_AS_STRING(out);
  const uint8_t *rp = (const uint8_t *)rs.buf;
  const uint8_t *pp = (const uint8_t *)pubs.buf;
  ossl_sha512_fn fast = no_ossl ? nullptr : ossl_sha512();
  // extract message pointers under the GIL, then hash WITHOUT it: this
  // loop is ~17 ms for a 10k batch and runs on the async pipeline's prep
  // path — holding the GIL here serializes prep against dispatch and
  // caps the stream at ~1/(prep+kernel) instead of 1/max(prep, kernel)
  std::vector<std::pair<const uint8_t *, size_t>> mv;
  mv.reserve((size_t)n);
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject *item = PySequence_Fast_GET_ITEM(seq, i);
    char *m;
    Py_ssize_t mlen;
    if (PyBytes_AsStringAndSize(item, &m, &mlen) < 0) {
      Py_DECREF(seq);
      Py_DECREF(out);
      PyBuffer_Release(&rs);
      PyBuffer_Release(&pubs);
      return nullptr;
    }
    mv.emplace_back((const uint8_t *)m, (size_t)mlen);
  }
  Py_BEGIN_ALLOW_THREADS
  std::vector<uint8_t> cat;
  for (Py_ssize_t i = 0; i < n; i++) {
    uint8_t digest[64];
    if (fast) {
      cat.resize(64 + mv[i].second);
      memcpy(cat.data(), rp + 32 * i, 32);
      memcpy(cat.data() + 32, pp + 32 * i, 32);
      if (mv[i].second) memcpy(cat.data() + 64, mv[i].first, mv[i].second);
      fast(cat.data(), cat.size(), digest);
    } else {
      sha512::Ctx c;
      sha512::init(&c);
      sha512::update(&c, rp + 32 * i, 32);
      sha512::update(&c, pp + 32 * i, 32);
      sha512::update(&c, mv[i].first, mv[i].second);
      sha512::final(&c, digest);
    }
    sha512::mod_l(digest, dst + 32 * i);
  }
  Py_END_ALLOW_THREADS
  Py_DECREF(seq);
  PyBuffer_Release(&rs);
  PyBuffer_Release(&pubs);
  return out;
}

// sr25519_verify_batch(ctx: bytes, pubs: n*32, sigs: n*64, msgs: seq)
//   -> bytes (n): 1 where R == [s]B - [k]A (schnorrkel verify), else 0.
// Transcript framing of sr25519_challenge_64; k = challenge mod L.
static PyObject *py_sr25519_verify_batch(PyObject *, PyObject *args) {
  const char *ctx_buf;
  Py_ssize_t ctx_len;
  Py_buffer pubs, sigs;
  PyObject *msgs;
  if (!PyArg_ParseTuple(args, "y#y*y*O", &ctx_buf, &ctx_len, &pubs, &sigs,
                        &msgs))
    return nullptr;
  PyObject *seq = PySequence_Fast(msgs, "expected a sequence of messages");
  if (!seq) {
    PyBuffer_Release(&pubs);
    PyBuffer_Release(&sigs);
    return nullptr;
  }
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  if (pubs.len < 32 * n || sigs.len < 64 * n) {
    Py_DECREF(seq);
    PyBuffer_Release(&pubs);
    PyBuffer_Release(&sigs);
    PyErr_SetString(PyExc_ValueError, "pubs/sigs must be n*32 / n*64 bytes");
    return nullptr;
  }
  PyObject *out = PyBytes_FromStringAndSize(nullptr, n);
  if (!out) {
    Py_DECREF(seq);
    PyBuffer_Release(&pubs);
    PyBuffer_Release(&sigs);
    return nullptr;
  }
  uint8_t *dst = (uint8_t *)PyBytes_AS_STRING(out);
  const uint8_t *pp = (const uint8_t *)pubs.buf;
  const uint8_t *sp = (const uint8_t *)sigs.buf;
  // message pointers are pinned under the GIL; the verification loop is
  // embarrassingly parallel and runs with the GIL RELEASED across a
  // small thread pool (each signature touches only its own output byte)
  std::vector<const uint8_t *> mptrs(n);
  std::vector<size_t> mlens(n);
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject *item = PySequence_Fast_GET_ITEM(seq, i);
    char *m;
    Py_ssize_t mlen;
    if (PyBytes_AsStringAndSize(item, &m, &mlen) < 0) {
      Py_DECREF(seq);
      Py_DECREF(out);
      PyBuffer_Release(&pubs);
      PyBuffer_Release(&sigs);
      return nullptr;
    }
    mptrs[i] = (const uint8_t *)m;
    mlens[i] = (size_t)mlen;
  }
  const uint8_t *ctx_p = (const uint8_t *)ctx_buf;
  size_t ctx_l = (size_t)ctx_len;

  auto verify_range = [&](Py_ssize_t lo, Py_ssize_t hi) {
    ed::point base;
    ed::fe_copy(base.x, ed::BASE_X_FE);
    ed::fe_copy(base.y, ed::BASE_Y_FE);
    ed::fe_one(base.z);
    ed::fe_copy(base.t, ed::BASE_T_FE);
    for (Py_ssize_t i = lo; i < hi; i++) {
      dst[i] = 0;
      const uint8_t *sig = sp + 64 * i;
      const uint8_t *pub = pp + 32 * i;
      if (!(sig[63] & 0x80)) continue;  // schnorrkel v1 marker
      uint8_t s_bytes[32];
      memcpy(s_bytes, sig + 32, 32);
      s_bytes[31] &= 0x7f;
      // s < L check (L = limbs sha512::L_LIMBS, little-endian u64)
      {
        uint64_t s_limbs[4];
        for (int j = 0; j < 4; j++) {
          s_limbs[j] = 0;
          for (int b = 0; b < 8; b++)
            s_limbs[j] |= (uint64_t)s_bytes[8 * j + b] << (8 * b);
        }
        bool lt = false, ge = false;
        for (int j = 3; j >= 0; j--) {
          if (s_limbs[j] < sha512::L_LIMBS[j]) { lt = true; break; }
          if (s_limbs[j] > sha512::L_LIMBS[j]) { ge = true; break; }
        }
        if (ge || !lt) continue;  // s >= L
      }
      ed::point A, R;
      if (!ed::ristretto_decode(A, pub)) continue;
      if (!ed::ristretto_decode(R, sig)) continue;
      // k = merlin challenge mod L (sr25519_challenge_64's framing)
      uint8_t k_wide[64], k_bytes[32];
      sr25519_challenge_64(ctx_p, ctx_l, mptrs[i], mlens[i], pub, sig, k_wide);
      sha512::mod_l(k_wide, k_bytes);
      // expected = [s]B + [k](-A); accept iff ristretto_eq(expected, R)
      ed::point sB, kA, negA, expected;
      ed::pt_scalar_mul(sB, s_bytes, base);
      ed::pt_neg(negA, A);
      ed::pt_scalar_mul(kA, k_bytes, negA);
      ed::pt_add(expected, sB, kA);
      dst[i] = ed::ristretto_eq(expected, R) ? 1 : 0;
    }
  };

  Py_BEGIN_ALLOW_THREADS
  // pool width: the affinity-mask CPU count (respects cpuset pinning),
  // overridable with TM_NATIVE_THREADS; hardware_concurrency() alone
  // oversubscribes cgroup-quota'd containers
  unsigned hw = 0;
  {
    cpu_set_t setmask;
    if (sched_getaffinity(0, sizeof(setmask), &setmask) == 0)
      hw = (unsigned)CPU_COUNT(&setmask);
    if (!hw) hw = std::thread::hardware_concurrency();
    const char *env = getenv("TM_NATIVE_THREADS");
    if (env && *env) {
      long v = strtol(env, nullptr, 10);
      if (v > 0 && v < 1024) hw = (unsigned)v;
    }
  }
  Py_ssize_t nthreads = (Py_ssize_t)(hw ? hw : 1);
  if (nthreads > n) nthreads = n > 0 ? n : 1;
  if (nthreads <= 1 || n < 16) {
    verify_range(0, n);
  } else {
    std::vector<std::thread> pool;
    Py_ssize_t chunk = (n + nthreads - 1) / nthreads;
    for (Py_ssize_t t = 0; t < nthreads; t++) {
      Py_ssize_t lo = t * chunk;
      Py_ssize_t hi = lo + chunk < n ? lo + chunk : n;
      if (lo >= hi) break;
      pool.emplace_back(verify_range, lo, hi);
    }
    for (auto &th : pool) th.join();
  }
  Py_END_ALLOW_THREADS

  Py_DECREF(seq);
  PyBuffer_Release(&pubs);
  PyBuffer_Release(&sigs);
  return out;
}

// --------------------------------------------------------------------------
// Host ed25519 RLC batch verification (the honest CPU batch baseline and
// the no-device fallback). Same construction as Go crypto/ed25519's batch
// path (crypto/ed25519/ed25519.go:192-227 -> curve25519-voi BatchVerifier):
// random 128-bit coefficients z_i, one cofactored check
//   [8]( sum z_i R_i + sum (z_i k_i mod L) A_i - [sum z_i s_i mod L] B ) == O
// evaluated with a Pippenger multi-scalar multiplication over 2n points.

#include <sys/random.h>

namespace ed {

// ZIP-215 edwards decompression (crypto/_edwards.py decompress with
// allow_noncanonical=True): y from the low 255 bits WITHOUT a y < p
// canonicity check, "negative zero" x accepted.
static bool ge_frombytes_zip215(point &out, const uint8_t in[32]) {
  fe y, yy, u, v, x;
  fe_frombytes(y, in);  // drops bit 255; value may be >= p (allowed)
  int sign = in[31] >> 7;
  fe_sq(yy, y);
  fe one;
  fe_one(one);
  fe_sub(u, yy, one);
  fe_carry(u);
  fe_mul(v, D_FE, yy);
  fe_add(v, v, one);
  fe_carry(v);
  if (!fe_sqrt_ratio(x, u, v)) return false;
  if (fe_is_negative(x) != (sign != 0)) fe_neg(x, x);
  fe_copy(out.x, x);
  fe_copy(out.y, y);
  fe_one(out.z);
  fe_mul(out.t, x, y);
  return true;
}

// 256-bit LE schoolbook product -> 64-byte LE -> mod L
static void sc_mul(uint8_t out[32], const uint8_t a[32], const uint8_t b[32]) {
  uint64_t al[4], bl[4];
  for (int i = 0; i < 4; i++) {
    al[i] = bl[i] = 0;
    for (int j = 0; j < 8; j++) {
      al[i] |= (uint64_t)a[8 * i + j] << (8 * j);
      bl[i] |= (uint64_t)b[8 * i + j] << (8 * j);
    }
  }
  uint64_t prod[8] = {0};
  for (int i = 0; i < 4; i++) {
    unsigned __int128 carry = 0;
    for (int j = 0; j < 4; j++) {
      unsigned __int128 cur =
          (unsigned __int128)al[i] * bl[j] + prod[i + j] + carry;
      prod[i + j] = (uint64_t)cur;
      carry = cur >> 64;
    }
    prod[i + 4] = (uint64_t)carry;
  }
  uint8_t wide[64];
  for (int i = 0; i < 8; i++)
    for (int j = 0; j < 8; j++) wide[8 * i + j] = (uint8_t)(prod[i] >> (8 * j));
  sha512::mod_l(wide, out);
}

// out = (a + b) mod L for a, b < L
static void sc_add(uint8_t out[32], const uint8_t a[32], const uint8_t b[32]) {
  uint64_t al[4], bl[4], s[4];
  for (int i = 0; i < 4; i++) {
    al[i] = bl[i] = 0;
    for (int j = 0; j < 8; j++) {
      al[i] |= (uint64_t)a[8 * i + j] << (8 * j);
      bl[i] |= (uint64_t)b[8 * i + j] << (8 * j);
    }
  }
  unsigned __int128 c = 0;
  for (int i = 0; i < 4; i++) {
    c += (unsigned __int128)al[i] + bl[i];
    s[i] = (uint64_t)c;
    c >>= 64;
  }
  // sum < 2L (< 2^253): one conditional subtract of L
  bool ge = c != 0;
  if (!ge) {
    ge = true;
    for (int i = 3; i >= 0; i--) {
      if (s[i] > sha512::L_LIMBS[i]) break;
      if (s[i] < sha512::L_LIMBS[i]) { ge = false; break; }
    }
  }
  if (ge) {
    uint64_t borrow = 0;
    for (int i = 0; i < 4; i++) {
      unsigned __int128 d =
          (unsigned __int128)s[i] - sha512::L_LIMBS[i] - borrow;
      s[i] = (uint64_t)d;
      borrow = (uint64_t)(d >> 64) ? 1 : 0;
    }
  }
  for (int i = 0; i < 4; i++)
    for (int j = 0; j < 8; j++) out[8 * i + j] = (uint8_t)(s[i] >> (8 * j));
}

// Pippenger MSM with 8-bit windows: res = sum scalars[i] * pts[i].
// Scalars are 32-byte LE (< L). ~n + 512 point adds per window.
static void pippenger_msm(point &res, const std::vector<uint8_t> &scalars,
                          const std::vector<point> &pts) {
  size_t n = pts.size();
  pt_identity(res);
  static thread_local std::vector<point> buckets(256);
  static thread_local std::vector<uint8_t> used(256);
  for (int w = 31; w >= 0; w--) {
    if (w != 31)
      for (int d = 0; d < 8; d++) pt_double(res, res);
    memset(used.data(), 0, 256);
    for (size_t i = 0; i < n; i++) {
      uint8_t dig = scalars[32 * i + w];
      if (!dig) continue;
      if (!used[dig]) {
        buckets[dig] = pts[i];
        used[dig] = 1;
      } else {
        pt_add(buckets[dig], buckets[dig], pts[i]);
      }
    }
    // sum_d d * bucket[d] via suffix sums
    point running, acc;
    pt_identity(running);
    pt_identity(acc);
    bool any = false;
    for (int d = 255; d >= 1; d--) {
      if (used[d]) {
        pt_add(running, running, buckets[d]);
        any = true;
      }
      if (any) pt_add(acc, acc, running);
    }
    if (any) pt_add(res, res, acc);
  }
}

// Full RLC batch verification; entries prevalidated by the caller except
// for the point decodes and s < L checks done here. Returns 1 (batch
// equation holds), 0 (reject — caller falls back per-sig for blame), or
// -1 on malformed input.
static int batch_verify_rlc(const uint8_t *pubs, const uint8_t *sigs,
                            const std::vector<std::pair<const uint8_t *, size_t>> &msgs) {
  size_t n = msgs.size();
  std::vector<point> pts;
  std::vector<uint8_t> scalars;
  pts.reserve(2 * n);
  scalars.reserve(64 * n);
  uint8_t s_sum[32] = {0};
  ossl_sha512_fn fast = ossl_sha512();
  std::vector<uint8_t> cat;
  // one bulk getrandom for every z coefficient (vs n syscalls in-loop)
  std::vector<uint8_t> zs_rand(16 * n);
  {
    size_t got = 0;
    while (got < zs_rand.size()) {
      ssize_t r = getrandom(zs_rand.data() + got, zs_rand.size() - got, 0);
      if (r <= 0) return -1;
      got += (size_t)r;
    }
  }
  static const uint8_t L_BYTES[32] = {
      0xed, 0xd3, 0xf5, 0x5c, 0x1a, 0x63, 0x12, 0x58, 0xd6, 0x9c, 0xf7,
      0xa2, 0xde, 0xf9, 0xde, 0x14, 0x00, 0x00, 0x00, 0x00, 0x00, 0x00,
      0x00, 0x00, 0x00, 0x00, 0x00, 0x00, 0x00, 0x00, 0x00, 0x10};
  for (size_t i = 0; i < n; i++) {
    const uint8_t *pub = pubs + 32 * i;
    const uint8_t *sig = sigs + 64 * i;
    // s < L (RFC 8032)
    bool lt = false;
    for (int j = 31; j >= 0; j--) {
      if (sig[32 + j] < L_BYTES[j]) { lt = true; break; }
      if (sig[32 + j] > L_BYTES[j]) return 0;
    }
    if (!lt) return 0;
    point A, R;
    if (!ge_frombytes_zip215(A, pub)) return 0;
    if (!ge_frombytes_zip215(R, sig)) return 0;
    // k = SHA512(R || A || M) mod L
    uint8_t digest[64], k[32];
    if (fast) {
      cat.resize(64 + msgs[i].second);
      memcpy(cat.data(), sig, 32);
      memcpy(cat.data() + 32, pub, 32);
      if (msgs[i].second) memcpy(cat.data() + 64, msgs[i].first, msgs[i].second);
      fast(cat.data(), cat.size(), digest);
    } else {
      sha512::Ctx c;
      sha512::init(&c);
      sha512::update(&c, sig, 32);
      sha512::update(&c, pub, 32);
      sha512::update(&c, msgs[i].first, msgs[i].second);
      sha512::final(&c, digest);
    }
    sha512::mod_l(digest, k);
    // random 128-bit z from the bulk fill
    uint8_t z[32] = {0};
    memcpy(z, zs_rand.data() + 16 * i, 16);
    uint8_t zs[32], zk[32];
    sc_mul(zs, z, sig + 32);
    sc_add(s_sum, s_sum, zs);
    sc_mul(zk, z, k);
    pts.push_back(R);
    scalars.insert(scalars.end(), z, z + 32);
    pts.push_back(A);
    scalars.insert(scalars.end(), zk, zk + 32);
  }
  point msm, sb, check;
  pippenger_msm(msm, scalars, pts);
  point base;
  fe_copy(base.x, BASE_X_FE);
  fe_copy(base.y, BASE_Y_FE);
  fe_one(base.z);
  fe_copy(base.t, BASE_T_FE);
  pt_scalar_mul(sb, s_sum, base);
  point neg_sb;
  pt_neg(neg_sb, sb);
  pt_add(check, msm, neg_sb);
  for (int d = 0; d < 3; d++) pt_double(check, check);  // cofactor 8
  return (fe_is_zero(check.x) && fe_eq(check.y, check.z)) ? 1 : 0;
}

}  // namespace ed

// ed25519_batch_verify(pubs: n*32, sigs: n*64, msgs: seq[bytes]) -> bool
//   One RLC batch equation over the whole input (crypto/ed25519/ed25519.go
//   :219-227 BatchVerifier.Verify semantics: a single cofactored check;
//   on False the caller re-verifies per signature for blame assignment).
static PyObject *py_ed25519_batch_verify(PyObject *, PyObject *args) {
  Py_buffer pubs, sigs;
  PyObject *msgs;
  if (!PyArg_ParseTuple(args, "y*y*O", &pubs, &sigs, &msgs)) return nullptr;
  PyObject *seq = PySequence_Fast(msgs, "expected a sequence of messages");
  if (!seq) {
    PyBuffer_Release(&pubs);
    PyBuffer_Release(&sigs);
    return nullptr;
  }
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  int rc = -1;
  if (pubs.len >= 32 * n && sigs.len >= 64 * n) {
    std::vector<std::pair<const uint8_t *, size_t>> mv;
    mv.reserve((size_t)n);
    bool ok = true;
    for (Py_ssize_t i = 0; i < n; i++) {
      char *m;
      Py_ssize_t mlen;
      if (PyBytes_AsStringAndSize(PySequence_Fast_GET_ITEM(seq, i), &m,
                                  &mlen) < 0) {
        ok = false;
        break;
      }
      mv.emplace_back((const uint8_t *)m, (size_t)mlen);
    }
    if (ok) {
      if (n == 0) {
        rc = 0;  // Verify() on an empty batch is false (batch.go:29)
      } else {
        Py_BEGIN_ALLOW_THREADS
        rc = ed::batch_verify_rlc((const uint8_t *)pubs.buf,
                                  (const uint8_t *)sigs.buf, mv);
        Py_END_ALLOW_THREADS
      }
    } else {
      Py_DECREF(seq);
      PyBuffer_Release(&pubs);
      PyBuffer_Release(&sigs);
      return nullptr;
    }
  } else {
    PyErr_SetString(PyExc_ValueError, "pubs/sigs shorter than n entries");
  }
  Py_DECREF(seq);
  PyBuffer_Release(&pubs);
  PyBuffer_Release(&sigs);
  if (rc < 0) {
    if (!PyErr_Occurred())
      PyErr_SetString(PyExc_RuntimeError, "batch verification failed to run");
    return nullptr;
  }
  return PyBool_FromLong(rc);
}

// vote_sign_bytes_batch(prefix, suffix, times: n*16B LE int64 pairs
// (seconds, nanos)) -> list[bytes]. Composes the canonical vote sign
// bytes for every signature of a commit in one call: delimited(prefix +
// Timestamp-field(5) + suffix), mirroring wire/canonical.py
// compose_vote_sign_bytes byte for byte (proto3 default-skip varints,
// 64-bit two's complement negatives). The per-signature Python composer
// measured ~27us/sig — the host bottleneck of pipelined header sync.
static size_t put_uvarint(uint8_t *dst, uint64_t v) {
  size_t i = 0;
  while (v >= 0x80) {
    dst[i++] = (uint8_t)(v | 0x80);
    v >>= 7;
  }
  dst[i++] = (uint8_t)v;
  return i;
}

static PyObject *py_vote_sign_bytes_batch(PyObject *, PyObject *args) {
  Py_buffer prefix, suffix, times;
  if (!PyArg_ParseTuple(args, "y*y*y*", &prefix, &suffix, &times))
    return nullptr;
  if (times.len % 16) {
    PyBuffer_Release(&prefix);
    PyBuffer_Release(&suffix);
    PyBuffer_Release(&times);
    PyErr_SetString(PyExc_ValueError,
                    "times must be n*16 bytes of (seconds, nanos) pairs");
    return nullptr;
  }
  Py_ssize_t n = times.len / 16;
  PyObject *out = PyList_New(n);
  if (!out) {
    PyBuffer_Release(&prefix);
    PyBuffer_Release(&suffix);
    PyBuffer_Release(&times);
    return nullptr;
  }
  const uint8_t *tp = (const uint8_t *)times.buf;
  std::vector<uint8_t> buf;
  for (Py_ssize_t i = 0; i < n; i++) {
    int64_t secs, nanos;
    memcpy(&secs, tp + 16 * i, 8);
    memcpy(&nanos, tp + 16 * i + 8, 8);
    uint8_t ts_body[22];
    size_t tn = 0;
    if (secs != 0) {
      ts_body[tn++] = 0x08;  // field 1, varint
      tn += put_uvarint(ts_body + tn, (uint64_t)secs);
    }
    if (nanos != 0) {
      ts_body[tn++] = 0x10;  // field 2, varint
      tn += put_uvarint(ts_body + tn, (uint64_t)nanos);
    }
    uint8_t mid[32];
    size_t mn = 0;
    mid[mn++] = 0x2a;  // field 5, length-delimited
    mn += put_uvarint(mid + mn, tn);
    memcpy(mid + mn, ts_body, tn);
    mn += tn;
    size_t body_len = (size_t)prefix.len + mn + (size_t)suffix.len;
    uint8_t hdr[10];
    size_t hn = put_uvarint(hdr, body_len);
    buf.resize(hn + body_len);
    memcpy(buf.data(), hdr, hn);
    memcpy(buf.data() + hn, prefix.buf, prefix.len);
    memcpy(buf.data() + hn + prefix.len, mid, mn);
    memcpy(buf.data() + hn + prefix.len + mn, suffix.buf, suffix.len);
    PyObject *b =
        PyBytes_FromStringAndSize((const char *)buf.data(), (Py_ssize_t)buf.size());
    if (!b) {
      Py_DECREF(out);
      PyBuffer_Release(&prefix);
      PyBuffer_Release(&suffix);
      PyBuffer_Release(&times);
      return nullptr;
    }
    PyList_SET_ITEM(out, i, b);
  }
  PyBuffer_Release(&prefix);
  PyBuffer_Release(&suffix);
  PyBuffer_Release(&times);
  return out;
}

// ed25519_rlc_scalars(s: n*32, k: n*32, z: n*32, m: int)
//   -> bytes ((n/m)*32 S-scalars || n*32 u-scalars)
//
// Host scalar prep for the DEVICE per-lane RLC fast-accept kernel
// (ops/pallas_rlc.py): lane g covers sigs j = g*m .. g*m+m-1 with
// coefficients c_0 = 1, c_j = z_j (random 128-bit, caller-supplied;
// slot-0 z entries are ignored). Per lane:
//   S   = (s_0 + sum_{j>=1} z_j * s_j) mod L
//   u_0 = k_0;  u_j = (z_j * k_j) mod L
// Same RLC construction as batch_verify_rlc above (crypto/ed25519/
// ed25519.go:192-227 semantics); the k inputs are already mod L, the s
// inputs may be >= L for invalid signatures (reduced here — the lane's
// s<L flag rejects them independently, this just keeps the math total).
static PyObject *py_ed25519_rlc_scalars(PyObject *, PyObject *args) {
  Py_buffer sb, kb, zb;
  Py_ssize_t m;
  if (!PyArg_ParseTuple(args, "y*y*y*n", &sb, &kb, &zb, &m)) return nullptr;
  Py_ssize_t n = sb.len / 32;
  if (m <= 0 || n % m || kb.len < 32 * n || zb.len < 32 * n) {
    PyBuffer_Release(&sb);
    PyBuffer_Release(&kb);
    PyBuffer_Release(&zb);
    PyErr_SetString(PyExc_ValueError, "bad rlc scalar input lengths");
    return nullptr;
  }
  Py_ssize_t g = n / m;
  PyObject *out = PyBytes_FromStringAndSize(nullptr, 32 * (g + n));
  if (!out) {
    PyBuffer_Release(&sb);
    PyBuffer_Release(&kb);
    PyBuffer_Release(&zb);
    return nullptr;
  }
  uint8_t *S = (uint8_t *)PyBytes_AS_STRING(out);
  uint8_t *U = S + 32 * g;
  const uint8_t *s = (const uint8_t *)sb.buf;
  const uint8_t *k = (const uint8_t *)kb.buf;
  const uint8_t *z = (const uint8_t *)zb.buf;
  Py_BEGIN_ALLOW_THREADS
  for (Py_ssize_t lane = 0; lane < g; lane++) {
    Py_ssize_t base = lane * m;
    // S init = s_0 mod L (s may be non-canonical; widen and reduce)
    uint8_t wide[64] = {0};
    memcpy(wide, s + 32 * base, 32);
    sha512::mod_l(wide, S + 32 * lane);
    memcpy(U + 32 * base, k + 32 * base, 32);
    for (Py_ssize_t j = 1; j < m; j++) {
      uint8_t zs[32];
      ed::sc_mul(zs, z + 32 * (base + j), s + 32 * (base + j));
      ed::sc_add(S + 32 * lane, S + 32 * lane, zs);
      ed::sc_mul(U + 32 * (base + j), z + 32 * (base + j), k + 32 * (base + j));
    }
  }
  Py_END_ALLOW_THREADS
  PyBuffer_Release(&sb);
  PyBuffer_Release(&kb);
  PyBuffer_Release(&zb);
  return out;
}

// --------------------------------------------------------------------------
// Columnar (EntryBlock) prep — the zero-copy commit path. All entry points
// below consume contiguous buffers (pubs n*32, sigs n*64, one concatenated
// sign-bytes buffer + an (n+1) int64 offset table) and run with the GIL
// RELEASED end to end: no per-signature Python objects are touched between
// commit selection and the kernel argument arrays (ops/entry_block.py).

// Shared per-range worker pool sizing (same policy as sr25519_verify_batch:
// affinity-mask CPU count, TM_NATIVE_THREADS override).
static unsigned native_pool_width() {
  unsigned hw = 0;
  cpu_set_t setmask;
  if (sched_getaffinity(0, sizeof(setmask), &setmask) == 0)
    hw = (unsigned)CPU_COUNT(&setmask);
  if (!hw) hw = std::thread::hardware_concurrency();
  const char *env = getenv("TM_NATIVE_THREADS");
  if (env && *env) {
    long v = strtol(env, nullptr, 10);
    if (v > 0 && v < 1024) hw = (unsigned)v;
  }
  return hw ? hw : 1;
}

template <typename Fn>
static void parallel_ranges(Py_ssize_t n, Py_ssize_t min_serial, Fn fn) {
  Py_ssize_t nthreads = (Py_ssize_t)native_pool_width();
  if (nthreads > n) nthreads = n > 0 ? n : 1;
  if (nthreads <= 1 || n < min_serial) {
    fn((Py_ssize_t)0, n);
    return;
  }
  std::vector<std::thread> pool;
  Py_ssize_t chunk = (n + nthreads - 1) / nthreads;
  for (Py_ssize_t t = 0; t < nthreads; t++) {
    Py_ssize_t lo = t * chunk;
    Py_ssize_t hi = lo + chunk < n ? lo + chunk : n;
    if (lo >= hi) break;
    pool.emplace_back(fn, lo, hi);
  }
  for (auto &th : pool) th.join();
}

// Offset-table validation shared by the columnar entry points. Runs
// before any GIL-released work: a non-monotonic table would make
// offs[i+1]-offs[i] wrap to a huge size_t inside the threaded hash loop.
static bool offsets_valid(const int64_t *op, Py_ssize_t n,
                          Py_ssize_t msgs_len) {
  if (n < 0) return false;
  if (n == 0) return true;
  if (op[0] != 0 || op[n] > msgs_len) return false;
  for (Py_ssize_t i = 0; i < n; i++)
    if (op[i + 1] < op[i]) return false;
  return true;
}

// k_i = SHA512(R_i || A_i || M_i) mod L over columnar buffers.
static void challenges_range(const uint8_t *rs, const uint8_t *pubs,
                             const uint8_t *msgs, const int64_t *offs,
                             Py_ssize_t lo, Py_ssize_t hi, uint8_t *dst,
                             ossl_sha512_fn fast) {
  std::vector<uint8_t> cat;
  for (Py_ssize_t i = lo; i < hi; i++) {
    size_t mlen = (size_t)(offs[i + 1] - offs[i]);
    const uint8_t *m = msgs + offs[i];
    uint8_t digest[64];
    if (fast) {
      cat.resize(64 + mlen);
      memcpy(cat.data(), rs + 32 * i, 32);
      memcpy(cat.data() + 32, pubs + 32 * i, 32);
      if (mlen) memcpy(cat.data() + 64, m, mlen);
      fast(cat.data(), cat.size(), digest);
    } else {
      sha512::Ctx c;
      sha512::init(&c);
      sha512::update(&c, rs + 32 * i, 32);
      sha512::update(&c, pubs + 32 * i, 32);
      sha512::update(&c, m, mlen);
      sha512::final(&c, digest);
    }
    sha512::mod_l(digest, dst + 32 * i);
  }
}

// 32B LE encoding -> 20 radix-2^13 limbs of the low 255 bits.
static inline void pack_limbs_row(const uint8_t enc[32], int32_t out[20]) {
  uint64_t w[4];
  for (int j = 0; j < 4; j++) {
    w[j] = 0;
    for (int b = 0; b < 8; b++) w[j] |= uint64_t(enc[8 * j + b]) << (8 * b);
  }
  w[3] &= 0x7fffffffffffffffULL;
  for (int limb = 0; limb < 20; limb++) {
    int bit = limb * 13;
    int word = bit >> 6, off = bit & 63;
    uint64_t v = w[word] >> off;
    if (off > 64 - 13 && word < 3) v |= w[word + 1] << (64 - off);
    out[limb] = int32_t(v & 0x1fff);
  }
}

static inline bool scalar_below_l(const uint8_t s[32]) {
  static const uint8_t L_BYTES[32] = {
      0xed, 0xd3, 0xf5, 0x5c, 0x1a, 0x63, 0x12, 0x58, 0xd6, 0x9c, 0xf7,
      0xa2, 0xde, 0xf9, 0xde, 0x14, 0x00, 0x00, 0x00, 0x00, 0x00, 0x00,
      0x00, 0x00, 0x00, 0x00, 0x00, 0x00, 0x00, 0x00, 0x00, 0x10};
  for (int j = 31; j >= 0; j--) {
    if (s[j] < L_BYTES[j]) return true;
    if (s[j] > L_BYTES[j]) return false;
  }
  return false;  // s == L
}

// ed25519_challenges_buf(rs: n*32, pubs: n*32, msgs: buffer,
//                        offsets: (n+1)*int64) -> bytes (n*32)
// Columnar variant of ed25519_challenges: the whole batch hashes in one
// GIL-released call with no PySequence walk (message i is
// msgs[offsets[i]:offsets[i+1]]).
static PyObject *py_ed25519_challenges_buf(PyObject *, PyObject *args) {
  Py_buffer rs, pubs, msgs, offs;
  int no_ossl = 0;  // tests force the scalar fallback path
  if (!PyArg_ParseTuple(args, "y*y*y*y*|p", &rs, &pubs, &msgs, &offs,
                        &no_ossl))
    return nullptr;
  Py_ssize_t n = offs.len / 8 - 1;
  const int64_t *op = (const int64_t *)offs.buf;
  bool ok = n >= 0 && offs.len % 8 == 0 && rs.len >= 32 * n &&
            pubs.len >= 32 * n && offsets_valid(op, n, msgs.len);
  PyObject *out = ok ? PyBytes_FromStringAndSize(nullptr, n * 32) : nullptr;
  if (!out) {
    PyBuffer_Release(&rs);
    PyBuffer_Release(&pubs);
    PyBuffer_Release(&msgs);
    PyBuffer_Release(&offs);
    if (ok) return nullptr;
    PyErr_SetString(PyExc_ValueError, "bad columnar challenge inputs");
    return nullptr;
  }
  uint8_t *dst = (uint8_t *)PyBytes_AS_STRING(out);
  const uint8_t *rp = (const uint8_t *)rs.buf;
  const uint8_t *pp = (const uint8_t *)pubs.buf;
  const uint8_t *mp = (const uint8_t *)msgs.buf;
  ossl_sha512_fn fast = no_ossl ? nullptr : ossl_sha512();
  Py_BEGIN_ALLOW_THREADS
  parallel_ranges(n, 2048, [&](Py_ssize_t lo, Py_ssize_t hi) {
    challenges_range(rp, pp, mp, op, lo, hi, dst, fast);
  });
  Py_END_ALLOW_THREADS
  PyBuffer_Release(&rs);
  PyBuffer_Release(&pubs);
  PyBuffer_Release(&msgs);
  PyBuffer_Release(&offs);
  return out;
}

// ed25519_prep_fused(pubs: n*32, sigs: n*64, msgs: buffer,
//                    offsets: (n+1)*int64, bucket) ->
//   (pub_limbs (bucket*20 i32), a_sign (bucket i32),
//    r_limbs (bucket*20 i32), r_sign (bucket i32),
//    s_bits (253*bucket i32, transposed), k_bits (253*bucket i32),
//    s_ok (bucket u8))
// The ENTIRE host prep of the XLA per-signature kernel (ops/backend.py
// prepare_batch: row pack + SHA-512 challenges + limb/bit pack + s<L) in
// one GIL-released native call. Padding lanes carry the identity layout
// (A = R = identity encoding, s = k = 0, s_ok = 1) like _pack_rows.
static PyObject *py_ed25519_prep_fused(PyObject *, PyObject *args) {
  Py_buffer pubs, sigs, msgs, offs;
  Py_ssize_t bucket;
  int no_ossl = 0;
  if (!PyArg_ParseTuple(args, "y*y*y*y*n|p", &pubs, &sigs, &msgs, &offs,
                        &bucket, &no_ossl))
    return nullptr;
  Py_ssize_t n = offs.len / 8 - 1;
  const int64_t *op = (const int64_t *)offs.buf;
  bool ok = n >= 0 && offs.len % 8 == 0 && bucket >= n && bucket > 0 &&
            pubs.len >= 32 * n && sigs.len >= 64 * n &&
            offsets_valid(op, n, msgs.len);
  if (!ok) {
    PyBuffer_Release(&pubs);
    PyBuffer_Release(&sigs);
    PyBuffer_Release(&msgs);
    PyBuffer_Release(&offs);
    PyErr_SetString(PyExc_ValueError, "bad fused prep inputs");
    return nullptr;
  }
  PyObject *pub_limbs = PyBytes_FromStringAndSize(nullptr, bucket * 20 * 4);
  PyObject *a_sign = PyBytes_FromStringAndSize(nullptr, bucket * 4);
  PyObject *r_limbs = PyBytes_FromStringAndSize(nullptr, bucket * 20 * 4);
  PyObject *r_sign = PyBytes_FromStringAndSize(nullptr, bucket * 4);
  PyObject *s_bits = PyBytes_FromStringAndSize(nullptr, 253 * bucket * 4);
  PyObject *k_bits = PyBytes_FromStringAndSize(nullptr, 253 * bucket * 4);
  PyObject *s_okb = PyBytes_FromStringAndSize(nullptr, bucket);
  if (!pub_limbs || !a_sign || !r_limbs || !r_sign || !s_bits || !k_bits ||
      !s_okb) {
    Py_XDECREF(pub_limbs); Py_XDECREF(a_sign); Py_XDECREF(r_limbs);
    Py_XDECREF(r_sign); Py_XDECREF(s_bits); Py_XDECREF(k_bits);
    Py_XDECREF(s_okb);
    PyBuffer_Release(&pubs);
    PyBuffer_Release(&sigs);
    PyBuffer_Release(&msgs);
    PyBuffer_Release(&offs);
    return nullptr;
  }
  int32_t *pl = (int32_t *)PyBytes_AS_STRING(pub_limbs);
  int32_t *as_ = (int32_t *)PyBytes_AS_STRING(a_sign);
  int32_t *rl = (int32_t *)PyBytes_AS_STRING(r_limbs);
  int32_t *rsn = (int32_t *)PyBytes_AS_STRING(r_sign);
  int32_t *sb = (int32_t *)PyBytes_AS_STRING(s_bits);
  int32_t *kb = (int32_t *)PyBytes_AS_STRING(k_bits);
  uint8_t *sok = (uint8_t *)PyBytes_AS_STRING(s_okb);
  const uint8_t *pp = (const uint8_t *)pubs.buf;
  const uint8_t *gp = (const uint8_t *)sigs.buf;
  const uint8_t *mp = (const uint8_t *)msgs.buf;
  ossl_sha512_fn fast = no_ossl ? nullptr : ossl_sha512();
  Py_BEGIN_ALLOW_THREADS
  // padding lanes first (bulk): zero bits/limbs, identity encodings
  memset(sb, 0, 253 * (size_t)bucket * 4);
  memset(kb, 0, 253 * (size_t)bucket * 4);
  memset(pl, 0, (size_t)bucket * 80);
  memset(rl, 0, (size_t)bucket * 80);
  memset(as_, 0, (size_t)bucket * 4);
  memset(rsn, 0, (size_t)bucket * 4);
  for (Py_ssize_t i = n; i < bucket; i++) {
    pl[20 * i] = 1;  // identity encoding y=1 -> limb0 = 1
    rl[20 * i] = 1;
    sok[i] = 1;
  }
  // per-row work is row-disjoint (the transposed bit arrays write column
  // i only), so the whole pack+hash pass fans out across the pool
  parallel_ranges(n, 1024, [&](Py_ssize_t lo, Py_ssize_t hi) {
    std::vector<uint8_t> cat;
    for (Py_ssize_t i = lo; i < hi; i++) {
      const uint8_t *pub = pp + 32 * i;
      const uint8_t *r = gp + 64 * i;
      const uint8_t *s = gp + 64 * i + 32;
      pack_limbs_row(pub, pl + 20 * i);
      pack_limbs_row(r, rl + 20 * i);
      as_[i] = pub[31] >> 7;
      rsn[i] = r[31] >> 7;
      sok[i] = scalar_below_l(s) ? 1 : 0;
      uint8_t digest[64], k[32];
      size_t mlen = (size_t)(op[i + 1] - op[i]);
      const uint8_t *m = mp + op[i];
      if (fast) {
        cat.resize(64 + mlen);
        memcpy(cat.data(), r, 32);
        memcpy(cat.data() + 32, pub, 32);
        if (mlen) memcpy(cat.data() + 64, m, mlen);
        fast(cat.data(), cat.size(), digest);
      } else {
        sha512::Ctx c;
        sha512::init(&c);
        sha512::update(&c, r, 32);
        sha512::update(&c, pub, 32);
        sha512::update(&c, m, mlen);
        sha512::final(&c, digest);
      }
      sha512::mod_l(digest, k);
      for (int b = 0; b < 253; b++) {
        sb[(Py_ssize_t)b * bucket + i] = (s[b >> 3] >> (b & 7)) & 1;
        kb[(Py_ssize_t)b * bucket + i] = (k[b >> 3] >> (b & 7)) & 1;
      }
    }
  });
  Py_END_ALLOW_THREADS
  PyBuffer_Release(&pubs);
  PyBuffer_Release(&sigs);
  PyBuffer_Release(&msgs);
  PyBuffer_Release(&offs);
  PyObject *tup = PyTuple_Pack(7, pub_limbs, a_sign, r_limbs, r_sign, s_bits,
                               k_bits, s_okb);
  Py_DECREF(pub_limbs); Py_DECREF(a_sign); Py_DECREF(r_limbs);
  Py_DECREF(r_sign); Py_DECREF(s_bits); Py_DECREF(k_bits); Py_DECREF(s_okb);
  return tup;
}

// ed25519_rlc_prep(pubs: n*32, sigs: n*64, msgs: buffer,
//                  offsets: (n+1)*int64, z: total*32, m, total) ->
//   (k_enc (n*32), S||U ((total/m + total)*32), s_ok (total u8))
// Fused host prep of the device RLC fast-accept kernel: SHA-512
// challenges + the per-lane 128x256-bit mod-L scalar mul-adds + s<L flags
// in one GIL-released call (ops/pallas_rlc.py prepare_rlc). total (a
// multiple of m, >= n) is the padded live-lane signature count; rows
// n..total-1 are padding lanes (s = k = 0, s_ok = 1, U = 0).
static PyObject *py_ed25519_rlc_prep(PyObject *, PyObject *args) {
  gil::enter();
  Py_buffer pubs, sigs, msgs, offs, zb;
  Py_ssize_t m, total;
  int no_ossl = 0;
  if (!PyArg_ParseTuple(args, "y*y*y*y*y*nn|p", &pubs, &sigs, &msgs, &offs,
                        &zb, &m, &total, &no_ossl))
    return nullptr;
  Py_ssize_t n = offs.len / 8 - 1;
  const int64_t *op = (const int64_t *)offs.buf;
  bool ok = n >= 0 && offs.len % 8 == 0 && m > 0 && total >= n &&
            total % m == 0 && pubs.len >= 32 * n && sigs.len >= 64 * n &&
            zb.len >= 32 * total && offsets_valid(op, n, msgs.len);
  PyObject *k_out = nullptr, *su_out = nullptr, *sok_out = nullptr;
  Py_ssize_t g = ok ? total / m : 0;
  if (ok) {
    k_out = PyBytes_FromStringAndSize(nullptr, n * 32);
    su_out = PyBytes_FromStringAndSize(nullptr, 32 * (g + total));
    sok_out = PyBytes_FromStringAndSize(nullptr, total);
  }
  if (!k_out || !su_out || !sok_out) {
    Py_XDECREF(k_out); Py_XDECREF(su_out); Py_XDECREF(sok_out);
    PyBuffer_Release(&pubs);
    PyBuffer_Release(&sigs);
    PyBuffer_Release(&msgs);
    PyBuffer_Release(&offs);
    PyBuffer_Release(&zb);
    if (!ok) PyErr_SetString(PyExc_ValueError, "bad rlc fused prep inputs");
    return nullptr;
  }
  uint8_t *kd = (uint8_t *)PyBytes_AS_STRING(k_out);
  uint8_t *S = (uint8_t *)PyBytes_AS_STRING(su_out);
  uint8_t *U = S + 32 * g;
  uint8_t *sok = (uint8_t *)PyBytes_AS_STRING(sok_out);
  const uint8_t *pp = (const uint8_t *)pubs.buf;
  const uint8_t *gp = (const uint8_t *)sigs.buf;
  const uint8_t *mp = (const uint8_t *)msgs.buf;
  const uint8_t *zp = (const uint8_t *)zb.buf;
  ossl_sha512_fn fast = no_ossl ? nullptr : ossl_sha512();
  GIL_FREE_BEGIN(ED25519_RLC_PREP)
  // lane-disjoint: each lane reads rows base..base+m-1 and writes only
  // its own S/U/k/s_ok slots
  parallel_ranges(g, 256, [&](Py_ssize_t lane_lo, Py_ssize_t lane_hi) {
    std::vector<uint8_t> cat;
    for (Py_ssize_t lane = lane_lo; lane < lane_hi; lane++) {
      Py_ssize_t base = lane * m;
      for (Py_ssize_t i = base; i < base + m && i < n; i++) {
        const uint8_t *pub = pp + 32 * i;
        const uint8_t *r = gp + 64 * i;
        sok[i] = scalar_below_l(gp + 64 * i + 32) ? 1 : 0;
        uint8_t digest[64];
        size_t mlen = (size_t)(op[i + 1] - op[i]);
        const uint8_t *msg = mp + op[i];
        if (fast) {
          cat.resize(64 + mlen);
          memcpy(cat.data(), r, 32);
          memcpy(cat.data() + 32, pub, 32);
          if (mlen) memcpy(cat.data() + 64, msg, mlen);
          fast(cat.data(), cat.size(), digest);
        } else {
          sha512::Ctx c;
          sha512::init(&c);
          sha512::update(&c, r, 32);
          sha512::update(&c, pub, 32);
          sha512::update(&c, msg, mlen);
          sha512::final(&c, digest);
        }
        sha512::mod_l(digest, kd + 32 * i);
      }
      for (Py_ssize_t i = base < n ? (base + m < n ? base + m : n) : base;
           i < base + m; i++)
        sok[i] = 1;  // padding rows: s = 0 < L
      // per-lane scalar mul-adds (ed25519_rlc_scalars semantics);
      // padding rows contribute s = k = 0 -> U = 0, no S term
      uint8_t wide[64] = {0};
      if (base < n) memcpy(wide, gp + 64 * base + 32, 32);
      sha512::mod_l(wide, S + 32 * lane);
      if (base < n)
        memcpy(U + 32 * base, kd + 32 * base, 32);
      else
        memset(U + 32 * base, 0, 32);
      for (Py_ssize_t j = 1; j < m; j++) {
        Py_ssize_t i = base + j;
        if (i >= n) {
          memset(U + 32 * i, 0, 32);
          continue;
        }
        uint8_t zs[32];
        ed::sc_mul(zs, zp + 32 * i, gp + 64 * i + 32);
        ed::sc_add(S + 32 * lane, S + 32 * lane, zs);
        ed::sc_mul(U + 32 * i, zp + 32 * i, kd + 32 * i);
      }
    }
  });
  GIL_FREE_END
  PyBuffer_Release(&pubs);
  PyBuffer_Release(&sigs);
  PyBuffer_Release(&msgs);
  PyBuffer_Release(&offs);
  PyBuffer_Release(&zb);
  PyObject *tup = PyTuple_Pack(3, k_out, su_out, sok_out);
  Py_DECREF(k_out); Py_DECREF(su_out); Py_DECREF(sok_out);
  return tup;
}

// vote_sign_bytes_batch_buf(prefix, suffix, times: n*16B LE int64 pairs)
//   -> (bytes buffer, bytes offsets ((n+1) int64 LE))
// Buffer-writing variant of vote_sign_bytes_batch: composes every
// signature's canonical sign bytes into ONE contiguous buffer + offset
// table (the EntryBlock msgs form) with the GIL released — no per-lane
// PyBytes objects or list handling.
static PyObject *py_vote_sign_bytes_batch_buf(PyObject *, PyObject *args) {
  Py_buffer prefix, suffix, times;
  if (!PyArg_ParseTuple(args, "y*y*y*", &prefix, &suffix, &times))
    return nullptr;
  if (times.len % 16) {
    PyBuffer_Release(&prefix);
    PyBuffer_Release(&suffix);
    PyBuffer_Release(&times);
    PyErr_SetString(PyExc_ValueError,
                    "times must be n*16 bytes of (seconds, nanos) pairs");
    return nullptr;
  }
  Py_ssize_t n = times.len / 16;
  const uint8_t *tp = (const uint8_t *)times.buf;
  PyObject *offs_out = PyBytes_FromStringAndSize(nullptr, (n + 1) * 8);
  if (!offs_out) {
    PyBuffer_Release(&prefix);
    PyBuffer_Release(&suffix);
    PyBuffer_Release(&times);
    return nullptr;
  }
  int64_t *offs = (int64_t *)PyBytes_AS_STRING(offs_out);
  // pass 1: exact per-record lengths -> offsets (GIL released; raw bufs)
  Py_BEGIN_ALLOW_THREADS
  offs[0] = 0;
  for (Py_ssize_t i = 0; i < n; i++) {
    int64_t secs, nanos;
    memcpy(&secs, tp + 16 * i, 8);
    memcpy(&nanos, tp + 16 * i + 8, 8);
    uint8_t scratch[10];
    size_t tn = 0;
    if (secs != 0) tn += 1 + put_uvarint(scratch, (uint64_t)secs);
    if (nanos != 0) tn += 1 + put_uvarint(scratch, (uint64_t)nanos);
    uint8_t tscratch[10];
    size_t mn = 1 + put_uvarint(tscratch, tn) + tn;
    size_t body = (size_t)prefix.len + mn + (size_t)suffix.len;
    size_t hn = put_uvarint(tscratch, body);
    offs[i + 1] = offs[i] + (int64_t)(hn + body);
  }
  Py_END_ALLOW_THREADS
  PyObject *buf_out = PyBytes_FromStringAndSize(nullptr, offs[n]);
  if (!buf_out) {
    Py_DECREF(offs_out);
    PyBuffer_Release(&prefix);
    PyBuffer_Release(&suffix);
    PyBuffer_Release(&times);
    return nullptr;
  }
  uint8_t *dst = (uint8_t *)PyBytes_AS_STRING(buf_out);
  Py_BEGIN_ALLOW_THREADS
  for (Py_ssize_t i = 0; i < n; i++) {
    int64_t secs, nanos;
    memcpy(&secs, tp + 16 * i, 8);
    memcpy(&nanos, tp + 16 * i + 8, 8);
    uint8_t ts_body[22];
    size_t tn = 0;
    if (secs != 0) {
      ts_body[tn++] = 0x08;
      tn += put_uvarint(ts_body + tn, (uint64_t)secs);
    }
    if (nanos != 0) {
      ts_body[tn++] = 0x10;
      tn += put_uvarint(ts_body + tn, (uint64_t)nanos);
    }
    uint8_t mid[32];
    size_t mn = 0;
    mid[mn++] = 0x2a;
    mn += put_uvarint(mid + mn, tn);
    memcpy(mid + mn, ts_body, tn);
    mn += tn;
    size_t body = (size_t)prefix.len + mn + (size_t)suffix.len;
    uint8_t *p = dst + offs[i];
    p += put_uvarint(p, body);
    memcpy(p, prefix.buf, prefix.len);
    p += prefix.len;
    memcpy(p, mid, mn);
    p += mn;
    memcpy(p, suffix.buf, suffix.len);
  }
  Py_END_ALLOW_THREADS
  PyBuffer_Release(&prefix);
  PyBuffer_Release(&suffix);
  PyBuffer_Release(&times);
  PyObject *tup = PyTuple_Pack(2, buf_out, offs_out);
  Py_DECREF(buf_out);
  Py_DECREF(offs_out);
  return tup;
}

// commit_prep_fused(flags: n u8, sigs: n*64, ts_secs: n*8 LE i64,
//                   ts_nanos: n*4 LE i32, pubs: n*32, power: n*8 LE i64,
//                   prefix_commit, prefix_nil, suffix,
//                   threshold, mode)
//   -> (sel (m*8 LE i64), tallied)                       when tally fails
//   -> (sel, tallied, pub (m*32), sig (m*64), msgs, offs ((m+1)*8))
//                                                        otherwise
//
// The ENTIRE commit-side host prep of types.verify_commit in one call
// over CommitBlock + ValidatorSet columns (ops/commit_prep.py), in three
// timed sections (gil::Free) with the outputs allocated between them:
// selection + tally, the sign-bytes sizes, sign bytes + gather. The two
// scans are ~1 ns a row (~10 us at 10 000 rows) and hold the GIL at every
// size. The third gives it up where it has COMMIT_PREP_RELEASE_ROWS
// selected rows or more, the size from which it is spread over threads
// (3.6 ms at 10 000 rows, through which the coalescer's own prep must be
// able to run), and holds below (at most a few tenths of a millisecond of
// one thread): a 150-signature commit keeps the GIL through its whole
// prep, where three releases had it queue behind every other caller to
// win the lock back for sections of 1 us and 20 us (PERF.md §6, PR 38).
// The stages: flag selection, voting-power tally vs the 2/3
// threshold (validation.go:152 loop semantics, incl. early-stop keeping
// the crossing lane), canonical sign-bytes composition into ONE
// contiguous buffer (vote_sign_bytes_batch_buf layout, prefix chosen per
// lane flag) and pub/sig row gather.
//
// mode bits: 1 = select COMMIT lanes only (else all non-ABSENT),
//            2 = tally only COMMIT lanes, 4 = early-stop past threshold.
static size_t uvarint_len(uint64_t v) {
  size_t i = 1;
  while (v >= 0x80) {
    v >>= 7;
    i++;
  }
  return i;
}

// The selected rows from which commit_prep_fused's third section is worth
// a hand-over of the GIL: parallel_ranges' grain there, so a section that
// runs on more than one thread lets go and a section of one thread holds.
static const Py_ssize_t COMMIT_PREP_RELEASE_ROWS = 1024;

static PyObject *py_commit_prep_fused(PyObject *, PyObject *args) {
  gil::enter();
  Py_buffer flags, sigs, tsec, tnan, pubs, power, pfxc, pfxn, sfx;
  Py_ssize_t threshold, mode;
  if (!PyArg_ParseTuple(args, "y*y*y*y*y*y*y*y*y*nn", &flags, &sigs, &tsec,
                        &tnan, &pubs, &power, &pfxc, &pfxn, &sfx, &threshold,
                        &mode))
    return nullptr;
  Py_ssize_t n = flags.len;
  auto release_all = [&]() {
    PyBuffer_Release(&flags);
    PyBuffer_Release(&sigs);
    PyBuffer_Release(&tsec);
    PyBuffer_Release(&tnan);
    PyBuffer_Release(&pubs);
    PyBuffer_Release(&power);
    PyBuffer_Release(&pfxc);
    PyBuffer_Release(&pfxn);
    PyBuffer_Release(&sfx);
  };
  if (sigs.len < 64 * n || tsec.len < 8 * n || tnan.len < 4 * n ||
      pubs.len < 32 * n || power.len < 8 * n) {
    release_all();
    PyErr_SetString(PyExc_ValueError, "bad commit prep inputs");
    return nullptr;
  }
  const uint8_t *fp = (const uint8_t *)flags.buf;
  const uint8_t *gp = (const uint8_t *)sigs.buf;
  const uint8_t *pp = (const uint8_t *)pubs.buf;
  const int64_t *sp = (const int64_t *)tsec.buf;
  const int32_t *np_ = (const int32_t *)tnan.buf;
  const int64_t *pw = (const int64_t *)power.buf;
  const bool sel_commit = mode & 1, count_fb = mode & 2, early = mode & 4;
  std::vector<int64_t> sel;
  int64_t tallied = 0;
  // a scan of the flags: never worth a hand-over
  GIL_HELD_IF(COMMIT_PREP_FUSED, true)
  sel.reserve((size_t)n);
  for (Py_ssize_t i = 0; i < n; i++) {
    uint8_t f = fp[i];
    if (sel_commit ? (f != 2) : (f == 1)) continue;
    sel.push_back((int64_t)i);
    if (!count_fb || f == 2) tallied += pw[i];
    if (early && tallied > (int64_t)threshold) break;
  }
  GIL_FREE_END
  Py_ssize_t m = (Py_ssize_t)sel.size();
  PyObject *sel_out = PyBytes_FromStringAndSize(
      (const char *)sel.data(), m * 8);
  if (!sel_out) {
    release_all();
    return nullptr;
  }
  if (tallied <= (int64_t)threshold) {
    release_all();
    PyObject *t = PyLong_FromLongLong((long long)tallied);
    PyObject *tup = t ? PyTuple_Pack(2, sel_out, t) : nullptr;
    Py_XDECREF(t);
    Py_DECREF(sel_out);
    return tup;
  }
  // pass 2: per-record sign-bytes lengths -> offsets
  PyObject *offs_out = PyBytes_FromStringAndSize(nullptr, (m + 1) * 8);
  if (!offs_out) {
    Py_DECREF(sel_out);
    release_all();
    return nullptr;
  }
  int64_t *offs = (int64_t *)PyBytes_AS_STRING(offs_out);
  // a scan of the selected rows: never worth one either
  GIL_HELD_IF(COMMIT_PREP_FUSED, true)
  offs[0] = 0;
  for (Py_ssize_t j = 0; j < m; j++) {
    Py_ssize_t i = (Py_ssize_t)sel[(size_t)j];
    uint64_t secs = (uint64_t)sp[i];
    uint64_t nanos = (uint64_t)(int64_t)np_[i];
    size_t tn = (secs ? 1 + uvarint_len(secs) : 0) +
                (nanos ? 1 + uvarint_len(nanos) : 0);
    size_t plen = fp[i] == 3 ? (size_t)pfxn.len : (size_t)pfxc.len;
    size_t body = plen + 1 + uvarint_len(tn) + tn + (size_t)sfx.len;
    offs[j + 1] = offs[j] + (int64_t)(uvarint_len(body) + body);
  }
  GIL_FREE_END
  PyObject *pub_out = PyBytes_FromStringAndSize(nullptr, m * 32);
  PyObject *sig_out = PyBytes_FromStringAndSize(nullptr, m * 64);
  PyObject *msgs_out = PyBytes_FromStringAndSize(nullptr, offs[m]);
  if (!pub_out || !sig_out || !msgs_out) {
    Py_XDECREF(pub_out); Py_XDECREF(sig_out); Py_XDECREF(msgs_out);
    Py_DECREF(sel_out); Py_DECREF(offs_out);
    release_all();
    return nullptr;
  }
  uint8_t *pub_d = (uint8_t *)PyBytes_AS_STRING(pub_out);
  uint8_t *sig_d = (uint8_t *)PyBytes_AS_STRING(sig_out);
  uint8_t *msg_d = (uint8_t *)PyBytes_AS_STRING(msgs_out);
  GIL_HELD_IF(COMMIT_PREP_FUSED, m < COMMIT_PREP_RELEASE_ROWS)
  parallel_ranges(m, COMMIT_PREP_RELEASE_ROWS,
                  [&](Py_ssize_t lo_j, Py_ssize_t hi_j) {
    for (Py_ssize_t j = lo_j; j < hi_j; j++) {
      Py_ssize_t i = (Py_ssize_t)sel[(size_t)j];
      memcpy(pub_d + 32 * j, pp + 32 * i, 32);
      memcpy(sig_d + 64 * j, gp + 64 * i, 64);
      // compose the canonical vote sign bytes (vote_sign_bytes_batch_buf
      // layout: delimited(prefix + Timestamp-field(5) + suffix))
      uint64_t secs = (uint64_t)sp[i];
      uint64_t nanos = (uint64_t)(int64_t)np_[i];
      uint8_t ts_body[22];
      size_t tn = 0;
      if (secs) {
        ts_body[tn++] = 0x08;
        tn += put_uvarint(ts_body + tn, secs);
      }
      if (nanos) {
        ts_body[tn++] = 0x10;
        tn += put_uvarint(ts_body + tn, nanos);
      }
      const uint8_t *pfx =
          fp[i] == 3 ? (const uint8_t *)pfxn.buf : (const uint8_t *)pfxc.buf;
      size_t plen = fp[i] == 3 ? (size_t)pfxn.len : (size_t)pfxc.len;
      uint8_t mid[32];
      size_t mn = 0;
      mid[mn++] = 0x2a;
      mn += put_uvarint(mid + mn, tn);
      memcpy(mid + mn, ts_body, tn);
      mn += tn;
      size_t body = plen + mn + (size_t)sfx.len;
      uint8_t *p = msg_d + offs[j];
      p += put_uvarint(p, body);
      memcpy(p, pfx, plen);
      p += plen;
      memcpy(p, mid, mn);
      p += mn;
      memcpy(p, sfx.buf, sfx.len);
    }
  });
  GIL_FREE_END
  release_all();
  PyObject *t = PyLong_FromLongLong((long long)tallied);
  PyObject *tup =
      t ? PyTuple_Pack(6, sel_out, t, pub_out, sig_out, msgs_out, offs_out)
        : nullptr;
  Py_XDECREF(t);
  Py_DECREF(sel_out); Py_DECREF(pub_out); Py_DECREF(sig_out);
  Py_DECREF(msgs_out); Py_DECREF(offs_out);
  return tup;
}

// sr25519_challenges_buf(ctx, pubs: n*32, rs: n*32, msgs: buffer,
//                        offsets: (n+1)*int64) -> n*32 bytes
// The schnorrkel challenge scalars k_i = merlin "sign:c" mod L of a
// columnar batch (ops/pallas_sr25519.py prepare_sr25519): the
// sr25519_challenge_64 transcript over one contiguous sign-bytes buffer,
// reduced mod L here. One timed section, which keeps the GIL below
// COMMIT_PREP_RELEASE_ROWS signatures (a 150-signature commit's pass is
// tens of microseconds) and is spread over threads without it above.
static PyObject *py_sr25519_challenges_buf(PyObject *, PyObject *args) {
  gil::enter();
  Py_buffer ctx, pubs, rs, msgs, offs;
  if (!PyArg_ParseTuple(args, "y*y*y*y*y*", &ctx, &pubs, &rs, &msgs, &offs))
    return nullptr;
  Py_ssize_t n = offs.len / 8 - 1;
  const int64_t *op = (const int64_t *)offs.buf;
  bool ok = n >= 0 && offs.len % 8 == 0 && pubs.len >= 32 * n &&
            rs.len >= 32 * n && offsets_valid(op, n, msgs.len);
  PyObject *out = ok ? PyBytes_FromStringAndSize(nullptr, n * 32) : nullptr;
  if (out) {
    uint8_t *dst = (uint8_t *)PyBytes_AS_STRING(out);
    const uint8_t *cp = (const uint8_t *)ctx.buf;
    const uint8_t *pp = (const uint8_t *)pubs.buf;
    const uint8_t *rp = (const uint8_t *)rs.buf;
    const uint8_t *mp = (const uint8_t *)msgs.buf;
    GIL_HELD_IF(SR25519_CHALLENGES, n < COMMIT_PREP_RELEASE_ROWS)
    parallel_ranges(n, COMMIT_PREP_RELEASE_ROWS,
                    [&](Py_ssize_t lo, Py_ssize_t hi) {
      uint8_t wide[64];
      for (Py_ssize_t i = lo; i < hi; i++) {
        sr25519_challenge_64(cp, (size_t)ctx.len, mp + op[i],
                             (size_t)(op[i + 1] - op[i]), pp + 32 * i,
                             rp + 32 * i, wide);
        sha512::mod_l(wide, dst + 32 * i);
      }
    });
    GIL_FREE_END
  }
  PyBuffer_Release(&ctx);
  PyBuffer_Release(&pubs);
  PyBuffer_Release(&rs);
  PyBuffer_Release(&msgs);
  PyBuffer_Release(&offs);
  if (!out && ok) return nullptr;
  if (!out) PyErr_SetString(PyExc_ValueError, "bad columnar challenge inputs");
  return out;
}

// --------------------------------------------------------------------------
// commit_decode_columns(data: bytes)
//   -> None
//   -> (height, round, block_id_raw, n, flags (n u8), sig (n*64),
//       ts_seconds (n*8 LE i64), ts_nanos (n*4 LE i32), addr (n*20))
//
// The wire bytes of a Commit (types/block.go:744; proto fields 1 height,
// 2 round, 3 block_id, 4 repeated CommitSig) parsed straight into the
// CommitBlock columns commit_prep_fused consumes, in one GIL-released
// walk. The fast path of types/block.py Commit.decode, whose Python walk
// (wire/proto.decode_message + _decode_sig_record) is the specification:
// this pass takes a strict subset of what that walk takes, computes the
// same values for it, and returns None for everything else — it never
// raises on content and never guesses. Taken:
//   outer   one-byte tags only, fields 1, 2 (varint) and 3 (bytes) at most
//           once, field 4 (bytes) repeated, in ascending order;
//   record  _decode_sig_record's canonical shape: fields 1 (varint), 2, 3,
//           4 (bytes) at most once each in any order; the timestamp of
//           varint fields 1, 2 at most once each; ABSENT lanes with no
//           address, no signature and the Go zero time; COMMIT/NIL lanes
//           with exactly 20 + 64 bytes; flag in {1, 2, 3};
//   varint  at most 10 bytes holding at most 64 bits (the Python walk
//           reads up to 70 bits into an unbounded int).
// Absent lanes are zero-filled. No state is kept between calls.
namespace commitdec {

static const int64_t GO_ZERO_TIME_SECONDS = -62135596800LL;

static inline bool uvarint(const uint8_t *&p, const uint8_t *end,
                           uint64_t &out) {
  uint64_t v = 0;
  for (int shift = 0; shift < 64; shift += 7) {
    if (p >= end) return false;
    uint8_t b = *p++;
    if (shift == 63 && b > 1) return false;
    v |= (uint64_t)(b & 0x7f) << shift;
    if (!(b & 0x80)) {
      out = v;
      return true;
    }
  }
  return false;
}

// a length-delimited value lying wholly before `end`
static inline bool delimited(const uint8_t *&p, const uint8_t *end,
                             const uint8_t *&body, size_t &len) {
  uint64_t ln;
  if (!uvarint(p, end, ln) || ln > (uint64_t)(end - p)) return false;
  body = p;
  len = (size_t)ln;
  p += ln;
  return true;
}

struct Outer {
  uint64_t height = 0, round = 0;
  const uint8_t *block_id = nullptr;
  size_t block_id_len = 0;
  const uint8_t *records = nullptr;  // tag byte of the first field 4
  size_t n = 0;
};

static bool scan_outer(const uint8_t *p, const uint8_t *end, Outer &o) {
  int last = 0;
  const uint8_t *body;
  size_t len;
  while (p < end) {
    const uint8_t *at = p;
    uint8_t tag = *p++;
    if (tag == 0x22) {
      if (last < 4) o.records = at;
      last = 4;
      if (!delimited(p, end, body, len)) return false;
      o.n++;
    } else if (tag == 0x08 && last < 1) {
      last = 1;
      if (!uvarint(p, end, o.height)) return false;
    } else if (tag == 0x10 && last < 2) {
      last = 2;
      if (!uvarint(p, end, o.round)) return false;
    } else if (tag == 0x1a && last < 3) {
      last = 3;
      if (!delimited(p, end, o.block_id, o.block_id_len)) return false;
    } else {
      return false;
    }
  }
  return true;
}

static bool parse_timestamp(const uint8_t *p, const uint8_t *end,
                            int64_t &secs, int32_t &nanos) {
  unsigned seen = 0;
  uint64_t v;
  while (p < end) {
    uint8_t tag = *p++;
    unsigned bit = tag == 0x08 ? 1u : tag == 0x10 ? 2u : 0u;
    if (!bit || (seen & bit) || !uvarint(p, end, v)) return false;
    seen |= bit;
    if (bit == 1)
      secs = (int64_t)v;  // wire/proto.to_signed64
    else
      nanos = (int32_t)(uint32_t)v;  // wire/proto.to_signed32
  }
  return true;
}

static bool parse_record(const uint8_t *p, const uint8_t *end, uint8_t *flag,
                         uint8_t *sig, int64_t *secs, int32_t *nanos,
                         uint8_t *addr) {
  uint64_t f = 0;
  const uint8_t *a = nullptr, *s = nullptr, *t = nullptr;
  size_t alen = 0, slen = 0, tlen = 0;
  unsigned seen = 0;
  while (p < end) {
    uint8_t tag = *p++;
    unsigned bit;
    bool ok;
    switch (tag) {
      case 0x08: bit = 1; ok = uvarint(p, end, f); break;
      case 0x12: bit = 2; ok = delimited(p, end, a, alen); break;
      case 0x1a: bit = 4; ok = delimited(p, end, t, tlen); break;
      case 0x22: bit = 8; ok = delimited(p, end, s, slen); break;
      default: return false;
    }
    if (!ok || (seen & bit)) return false;
    seen |= bit;
  }
  int64_t sec = 0;
  int32_t nan = 0;
  if ((seen & 4) && !parse_timestamp(t, t + tlen, sec, nan)) return false;
  if (f == 1) {  // BLOCK_ID_FLAG_ABSENT
    if (alen || slen || sec != GO_ZERO_TIME_SECONDS || nan != 0) return false;
    memset(sig, 0, 64);
    memset(addr, 0, 20);
  } else if (f == 2 || f == 3) {  // COMMIT, NIL
    if (alen != 20 || slen != 64) return false;
    memcpy(sig, s, 64);
    memcpy(addr, a, 20);
  } else {
    return false;
  }
  *flag = (uint8_t)f;
  *secs = sec;
  *nanos = nan;
  return true;
}

}  // namespace commitdec

static PyObject *py_commit_decode_columns(PyObject *, PyObject *arg) {
  gil::enter();
  // bytes only: the Python walk hands slices of its input on (bytearray
  // and memoryview slices are other types than these columns' bytes)
  if (!PyBytes_Check(arg)) Py_RETURN_NONE;
  const uint8_t *data = (const uint8_t *)PyBytes_AS_STRING(arg);
  const uint8_t *end = data + PyBytes_GET_SIZE(arg);
  commitdec::Outer o;
  // columns in one block: ts_seconds | ts_nanos | sig | addr | flags, so
  // the 8- and 4-byte lanes are aligned; copied out under the GIL below
  uint8_t *cols = nullptr;
  bool ok;
  GIL_FREE_BEGIN(COMMIT_DECODE_COLUMNS)
  ok = commitdec::scan_outer(data, end, o);
  if (ok && o.n) {
    cols = (uint8_t *)malloc(o.n * (8 + 4 + 64 + 20 + 1));
    ok = cols != nullptr;
    int64_t *secs = (int64_t *)cols;
    int32_t *nanos = (int32_t *)(cols + o.n * 8);
    uint8_t *sig = cols + o.n * 12, *addr = cols + o.n * 76,
            *flags = cols + o.n * 96;
    const uint8_t *p = o.records, *body;
    size_t len;
    for (size_t i = 0; ok && i < o.n; i++) {
      p++;  // the 0x22 tag scan_outer saw
      ok = commitdec::delimited(p, end, body, len) &&
           commitdec::parse_record(body, body + len, flags + i, sig + 64 * i,
                                   secs + i, nanos + i, addr + 20 * i);
    }
  }
  GIL_FREE_END
  if (!ok) {
    free(cols);
    Py_RETURN_NONE;
  }
  // "y#" turns a null pointer into None: empty values point at ""
  const char *c = cols ? (const char *)cols : "";
  const char *bid = o.block_id ? (const char *)o.block_id : "";
  Py_ssize_t n = (Py_ssize_t)o.n;
  PyObject *tup = Py_BuildValue(
      "(Liy#ny#y#y#y#y#)", (long long)(int64_t)o.height,
      (int)(int32_t)(uint32_t)o.round, bid, (Py_ssize_t)o.block_id_len, n,
      c + n * 96, n, c + n * 12, n * 64, c, n * 8, c + n * 8, n * 4,
      c + n * 76, n * 20);
  free(cols);
  return tup;
}

// --------------------------------------------------------------------------
// valset_decode_columns(data: bytes)
//   -> None
//   -> (n, addr (n*20), pub (n*32), power (n*8 LE i64),
//       priority (n*8 LE i64), proposer_addr (20), proposer_pub (32),
//       proposer_power, proposer_priority)
//
// The wire bytes of a ValidatorSet (proto fields 1 repeated Validator,
// 2 proposer, 3 total_voting_power — parsed and dropped: the set's total
// is recomputed, never trusted) into columns, in one GIL-released walk.
// The fast path of types/validator_set.py ValidatorSet.decode, whose
// Python walk (decode_message + Validator.decode + pubkey_from_proto) is
// the specification, as Commit.decode's is for commit_decode_columns: a
// strict subset of what that walk decodes WITHOUT raising, the same
// values for it, None for everything else. Taken:
//   outer      one-byte tags only: field 1 (bytes) once or more, field 2
//              (bytes) exactly once, field 3 (varint) at most once, in any
//              order;
//   validator  fields 1 address (bytes, exactly 20), 2 pub_key (bytes),
//              3 voting_power, 4 proposer_priority (varints) at most once
//              each in any order; address and key present; power >= 0;
//   pub_key    exactly one field: 1 ed25519 (bytes, exactly 32) — any
//              other member of the PublicKey oneof keeps the walk;
//   varint     commitdec's: at most 10 bytes holding at most 64 bits,
//              read as int64 like wire/proto.to_signed64.
// No state is kept between calls.
namespace valsetdec {

using commitdec::delimited;
using commitdec::uvarint;

static bool parse_validator(const uint8_t *p, const uint8_t *end,
                            uint8_t *addr, uint8_t *pub, int64_t *power,
                            int64_t *priority) {
  const uint8_t *a = nullptr, *k = nullptr;
  size_t alen = 0, klen = 0;
  uint64_t pw = 0, pr = 0;
  unsigned seen = 0;
  while (p < end) {
    uint8_t tag = *p++;
    unsigned bit;
    bool ok;
    switch (tag) {
      case 0x0a: bit = 1; ok = delimited(p, end, a, alen); break;
      case 0x12: bit = 2; ok = delimited(p, end, k, klen); break;
      case 0x18: bit = 4; ok = uvarint(p, end, pw); break;
      case 0x20: bit = 8; ok = uvarint(p, end, pr); break;
      default: return false;
    }
    if (!ok || (seen & bit)) return false;
    seen |= bit;
  }
  // PublicKey{1: ed25519}: tag, length 32, the key — and nothing else
  if (alen != 20 || klen != 34 || k[0] != 0x0a || k[1] != 32) return false;
  if ((int64_t)pw < 0) return false;
  memcpy(addr, a, 20);
  memcpy(pub, k + 2, 32);
  *power = (int64_t)pw;
  *priority = (int64_t)pr;
  return true;
}

// one walk of the outer message. The first (`cols` null) checks the outer
// shape and counts the validators into `n`; the second is given that `n`
// and a block of n * 68 bytes, and parses every validator and the proposer
static bool walk(const uint8_t *p, const uint8_t *end, size_t &n,
                 uint8_t *cols, uint8_t *prop, int64_t *prop_nums) {
  size_t total = n, i = 0;
  unsigned seen = 0;
  const uint8_t *body;
  size_t len;
  uint64_t v;
  while (p < end) {
    uint8_t tag = *p++;
    if (tag == 0x0a) {
      if (!delimited(p, end, body, len)) return false;
      if (cols &&
          !parse_validator(body, body + len, cols + 48 * total + 20 * i,
                           cols + 16 * total + 32 * i, (int64_t *)cols + i,
                           (int64_t *)cols + total + i))
        return false;
      i++;
    } else if (tag == 0x12 && !(seen & 1)) {
      seen |= 1;
      if (!delimited(p, end, body, len)) return false;
      if (cols && !parse_validator(body, body + len, prop, prop + 20,
                                   prop_nums, prop_nums + 1))
        return false;
    } else if (tag == 0x18 && !(seen & 2)) {
      seen |= 2;
      if (!uvarint(p, end, v)) return false;
    } else {
      return false;
    }
  }
  n = i;
  return i > 0 && (seen & 1);
}

}  // namespace valsetdec

static PyObject *py_valset_decode_columns(PyObject *, PyObject *arg) {
  gil::enter();
  // bytes only, as commit_decode_columns: the walk's addresses are slices
  // of its input, of the input's own type
  if (!PyBytes_Check(arg)) Py_RETURN_NONE;
  const uint8_t *data = (const uint8_t *)PyBytes_AS_STRING(arg);
  const uint8_t *end = data + PyBytes_GET_SIZE(arg);
  // columns in one block: power | priority | pub | addr, so the 8-byte
  // lanes are aligned; copied out under the GIL below
  uint8_t *cols = nullptr;
  uint8_t prop[52];
  int64_t prop_nums[2] = {0, 0};
  size_t n = 0;
  bool ok;
  GIL_FREE_BEGIN(VALSET_DECODE_COLUMNS)
  ok = valsetdec::walk(data, end, n, nullptr, nullptr, nullptr);
  if (ok) {
    cols = (uint8_t *)malloc(n * 68);
    ok = cols != nullptr &&
         valsetdec::walk(data, end, n, cols, prop, prop_nums);
  }
  GIL_FREE_END
  if (!ok) {
    free(cols);
    Py_RETURN_NONE;
  }
  Py_ssize_t k = (Py_ssize_t)n;
  const char *c = (const char *)cols;
  PyObject *tup = Py_BuildValue(
      "(ny#y#y#y#y#y#LL)", k, c + k * 48, k * 20, c + k * 16, k * 32, c,
      k * 8, c + k * 8, k * 8, (const char *)prop, (Py_ssize_t)20,
      (const char *)prop + 20, (Py_ssize_t)32, (long long)prop_nums[0],
      (long long)prop_nums[1]);
  free(cols);
  return tup;
}

static PyMethodDef Methods[] = {
    {"commit_prep_fused", py_commit_prep_fused, METH_VARARGS,
     "Fused columnar commit prep: selection + tally + sign-bytes + "
     "pub/sig gather in one call (three timed sections; the GIL is given "
     "up in the last alone, from 1 024 selected rows)"},
    {"ed25519_batch_verify", py_ed25519_batch_verify, METH_VARARGS,
     "Host RLC batch ed25519 verification (Pippenger MSM); returns bool"},
    {"ed25519_rlc_scalars", py_ed25519_rlc_scalars, METH_VARARGS,
     "Per-lane RLC scalar prep for the device fast-accept kernel"},
    {"vote_sign_bytes_batch", py_vote_sign_bytes_batch, METH_VARARGS,
     "Batch canonical vote sign-bytes composition from a template"},
    {"ed25519_challenges", py_ed25519_challenges, METH_VARARGS,
     "Batch k = SHA512(R||A||M) mod L challenge scalars (32B LE each)"},
    {"ed25519_challenges_buf", py_ed25519_challenges_buf, METH_VARARGS,
     "Columnar challenge scalars from a concatenated msgs buffer + offsets"},
    {"ed25519_prep_fused", py_ed25519_prep_fused, METH_VARARGS,
     "Fused columnar host prep for the XLA per-sig kernel (one GIL-released call)"},
    {"ed25519_rlc_prep", py_ed25519_rlc_prep, METH_VARARGS,
     "Fused columnar challenges + per-lane RLC scalar prep + s<L flags"},
    {"vote_sign_bytes_batch_buf", py_vote_sign_bytes_batch_buf, METH_VARARGS,
     "Batch sign-bytes composed into one contiguous buffer + offset table"},
    {"sr25519_verify_batch", py_sr25519_verify_batch, METH_VARARGS,
     "Batch schnorrkel sr25519 verification (R == [s]B - [k]A)"},
    {"merkle_root", py_merkle_root, METH_VARARGS,
     "RFC-6962 merkle root of a list of byte strings"},
    {"sha256_many", py_sha256_many, METH_VARARGS,
     "SHA-256 of each item, concatenated"},
    {"pack_le_limbs", py_pack_le_limbs, METH_VARARGS,
     "pack 32B LE encodings into 13-bit limb arrays"},
    {"sr25519_challenges_buf", py_sr25519_challenges_buf, METH_VARARGS,
     "Columnar sr25519 challenge scalars (mod L), one timed section"},
    {"pack_bits_le", py_pack_bits_le, METH_VARARGS,
     "pack 32B LE scalars into transposed bit arrays"},
    {"commit_decode_columns", py_commit_decode_columns, METH_O,
     "Commit wire bytes -> CommitBlock columns in one GIL-released walk; "
     "None for any input off the canonical shape"},
    {"valset_decode_columns", py_valset_decode_columns, METH_O,
     "ValidatorSet wire bytes -> address/key/power/priority columns in one "
     "GIL-released walk; None for any input off the canonical shape"},
    {"gil_stats", py_gil_stats, METH_NOARGS,
     "{entry: (sections, free_s, wait_s, held)} of the entries that time "
     "their sections (those that gave the GIL up; the count that held it): "
     "process-wide, always on, only rises"},
    {"last_sections", py_last_sections, METH_NOARGS,
     "[(t_released, t_wanted, t_got, held), ...] in perf_counter seconds: "
     "the sections of the calling thread's last call of such an entry"},
    {nullptr, nullptr, 0, nullptr}};

static struct PyModuleDef moduledef = {PyModuleDef_HEAD_INIT, "tm_native",
                                       nullptr, -1, Methods};

PyMODINIT_FUNC PyInit_tm_native(void) { return PyModule_Create(&moduledef); }
