/* Batched SHA-512 for the host side of the TPU signature verifier.
 *
 * The verifier's only per-signature host work is the challenge hash
 * k = SHA-512(R || A || M); everything else lives on device. This file
 * implements FIPS 180-4 SHA-512 from the spec and exposes one batch
 * entry point that hashes N variable-length messages (concatenated
 * buffer + offsets) into N 64-byte digests, parallelized with OpenMP
 * from PARALLEL_MIN_BATCH messages up. The prefixed entry the verifier
 * uses (sha512_batch_prefixed_mod_l) hands back each digest already
 * reduced mod the group order L: the challenge scalar leaves this file
 * as the 32 bytes the kernels take.
 *
 * Replaces the reference's reliance on Go's crypto/sha512 inside
 * curve25519-voi's batch verifier (crypto/ed25519/ed25519.go:198-233).
 *
 * Build: cc -O3 -shared -fPIC -fopenmp sha512_batch.c -o libsha512batch.so
 */

#include <stdint.h>
#include <string.h>

#ifdef _OPENMP
#include <omp.h>
#endif

/* Below this many messages a batch is hashed by the calling thread
 * alone. A parallel region wakes one worker a core, and libgomp's
 * workers spin for milliseconds after it ends: on a host whose cores
 * they fill, the accelerator runtime's transfer thread then waits ~4.5
 * ms for a core in about half the calls (a 150-signature commit read
 * 7.3 or 11.9 ms for it; PERF.md section 6, PR 29). Hashing 1,024 vote
 * messages serially takes about a millisecond. */
#define PARALLEL_MIN_BATCH 1024

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

#define ROTR(x, n) (((x) >> (n)) | ((x) << (64 - (n))))

static void sha512_compress(uint64_t h[8], const uint8_t block[128]) {
  uint64_t w[80];
  for (int i = 0; i < 16; i++) {
    w[i] = ((uint64_t)block[i * 8] << 56) | ((uint64_t)block[i * 8 + 1] << 48) |
           ((uint64_t)block[i * 8 + 2] << 40) |
           ((uint64_t)block[i * 8 + 3] << 32) |
           ((uint64_t)block[i * 8 + 4] << 24) |
           ((uint64_t)block[i * 8 + 5] << 16) |
           ((uint64_t)block[i * 8 + 6] << 8) | (uint64_t)block[i * 8 + 7];
  }
  for (int i = 16; i < 80; i++) {
    uint64_t s0 = ROTR(w[i - 15], 1) ^ ROTR(w[i - 15], 8) ^ (w[i - 15] >> 7);
    uint64_t s1 = ROTR(w[i - 2], 19) ^ ROTR(w[i - 2], 61) ^ (w[i - 2] >> 6);
    w[i] = w[i - 16] + s0 + w[i - 7] + s1;
  }
  uint64_t a = h[0], b = h[1], c = h[2], d = h[3];
  uint64_t e = h[4], f = h[5], g = h[6], hh = h[7];
  for (int i = 0; i < 80; i++) {
    uint64_t S1 = ROTR(e, 14) ^ ROTR(e, 18) ^ ROTR(e, 41);
    uint64_t ch = (e & f) ^ (~e & g);
    uint64_t t1 = hh + S1 + ch + K[i] + w[i];
    uint64_t S0 = ROTR(a, 28) ^ ROTR(a, 34) ^ ROTR(a, 39);
    uint64_t maj = (a & b) ^ (a & c) ^ (b & c);
    uint64_t t2 = S0 + maj;
    hh = g; g = f; f = e; e = d + t1;
    d = c; c = b; b = a; a = t1 + t2;
  }
  h[0] += a; h[1] += b; h[2] += c; h[3] += d;
  h[4] += e; h[5] += f; h[6] += g; h[7] += hh;
}

static void sha512_one(const uint8_t *msg, uint64_t len, uint8_t out[64]) {
  uint64_t h[8] = {0x6a09e667f3bcc908ULL, 0xbb67ae8584caa73bULL,
                   0x3c6ef372fe94f82bULL, 0xa54ff53a5f1d36f1ULL,
                   0x510e527fade682d1ULL, 0x9b05688c2b3e6c1fULL,
                   0x1f83d9abfb41bd6bULL, 0x5be0cd19137e2179ULL};
  uint64_t i = 0;
  for (; i + 128 <= len; i += 128) sha512_compress(h, msg + i);
  uint8_t tail[256];
  uint64_t rem = len - i;
  memcpy(tail, msg + i, rem);
  tail[rem] = 0x80;
  uint64_t padlen = (rem < 112) ? 128 : 256;
  memset(tail + rem + 1, 0, padlen - rem - 1 - 16);
  /* messages here are far below 2^61 bytes: high 64 bits of length = 0 */
  memset(tail + padlen - 16, 0, 8);
  uint64_t bits = len * 8;
  for (int j = 0; j < 8; j++)
    tail[padlen - 1 - j] = (uint8_t)(bits >> (8 * j));
  sha512_compress(h, tail);
  if (padlen == 256) sha512_compress(h, tail + 128);
  for (int j = 0; j < 8; j++)
    for (int b = 0; b < 8; b++)
      out[j * 8 + b] = (uint8_t)(h[j] >> (56 - 8 * b));
}

/* Hash n messages. buf holds all messages concatenated; offsets has n+1
 * entries (message i is buf[offsets[i] .. offsets[i+1])). Digests are
 * written to out (n * 64 bytes). */
void sha512_batch(const uint8_t *buf, const uint64_t *offsets, int64_t n,
                  uint8_t *out) {
#ifdef _OPENMP
#pragma omp parallel for schedule(static) if (n >= PARALLEL_MIN_BATCH)
#endif
  for (int64_t i = 0; i < n; i++) {
    sha512_one(buf + offsets[i], offsets[i + 1] - offsets[i],
               out + (uint64_t)i * 64);
  }
}

/* Streaming variant used by the prefixed batch at the end. */
typedef struct {
  uint64_t h[8];
  uint8_t buf[128];
  uint64_t buflen;
  uint64_t total;
} sha512_ctx;

static void sha512_init(sha512_ctx *c) {
  static const uint64_t iv[8] = {
      0x6a09e667f3bcc908ULL, 0xbb67ae8584caa73bULL, 0x3c6ef372fe94f82bULL,
      0xa54ff53a5f1d36f1ULL, 0x510e527fade682d1ULL, 0x9b05688c2b3e6c1fULL,
      0x1f83d9abfb41bd6bULL, 0x5be0cd19137e2179ULL};
  memcpy(c->h, iv, sizeof(iv));
  c->buflen = 0;
  c->total = 0;
}

static void sha512_update(sha512_ctx *c, const uint8_t *p, uint64_t len) {
  c->total += len;
  if (c->buflen) {
    uint64_t take = 128 - c->buflen;
    if (take > len) take = len;
    memcpy(c->buf + c->buflen, p, take);
    c->buflen += take;
    p += take;
    len -= take;
    if (c->buflen == 128) {
      sha512_compress(c->h, c->buf);
      c->buflen = 0;
    }
  }
  for (; len >= 128; p += 128, len -= 128) sha512_compress(c->h, p);
  if (len) {
    memcpy(c->buf, p, len);
    c->buflen = len;
  }
}

static void sha512_final(sha512_ctx *c, uint8_t out[64]) {
  uint64_t rem = c->buflen;
  uint8_t tail[256];
  memcpy(tail, c->buf, rem);
  tail[rem] = 0x80;
  uint64_t padlen = (rem < 112) ? 128 : 256;
  memset(tail + rem + 1, 0, padlen - rem - 1 - 16);
  memset(tail + padlen - 16, 0, 8);
  uint64_t bits = c->total * 8;
  for (int j = 0; j < 8; j++) tail[padlen - 1 - j] = (uint8_t)(bits >> (8 * j));
  sha512_compress(c->h, tail);
  if (padlen == 256) sha512_compress(c->h, tail + 128);
  for (int j = 0; j < 8; j++)
    for (int b = 0; b < 8; b++)
      out[j * 8 + b] = (uint8_t)(c->h[j] >> (56 - 8 * b));
}

/* --- reduction mod L --------------------------------------------------------
 *
 * A 512-bit little-endian value mod the ed25519 group order
 * L = 2^252 + 27742317777372353535851937790883648493, by Barrett's
 * method (Handbook of Applied Cryptography 14.42) on 64-bit limbs:
 * with b = 2^64, k = 4 and mu = floor(b^(2k) / L),
 *   q = floor(floor(x / b^(k-1)) * mu / b^(k+1)),  r = x - q*L < 3L,
 * computed mod b^(k+1) and followed by at most two subtractions of L.
 * The result is the one residue in [0, L): byte for byte
 * int.from_bytes(digest, "little") % L. Not constant time, and need
 * not be: a challenge is public. */

typedef unsigned __int128 u128;

static const uint64_t L_LIMBS[5] = {0x5812631a5cf5d3edULL, 0x14def9dea2f79cd6ULL,
                                    0, 0x1000000000000000ULL, 0};
static const uint64_t MU_LIMBS[5] = {0xed9ce5a30a2c131bULL, 0x2106215d086329a7ULL,
                                     0xffffffffffffffebULL, 0xffffffffffffffffULL,
                                     0xf};

/* out[0 .. na+nb) = a * b, schoolbook. */
static void mul_limbs(const uint64_t *a, int na, const uint64_t *b, int nb,
                      uint64_t *out) {
  memset(out, 0, (size_t)(na + nb) * sizeof(uint64_t));
  for (int i = 0; i < na; i++) {
    uint64_t carry = 0;
    for (int j = 0; j < nb; j++) {
      u128 t = (u128)a[i] * b[j] + out[i + j] + carry;
      out[i + j] = (uint64_t)t;
      carry = (uint64_t)(t >> 64);
    }
    out[i + nb] = carry;
  }
}

/* out = (a - b) mod 2^320; returns the borrow out of the top limb. */
static uint64_t sub5(const uint64_t a[5], const uint64_t b[5], uint64_t out[5]) {
  uint64_t borrow = 0;
  for (int i = 0; i < 5; i++) {
    u128 t = (u128)a[i] - b[i] - borrow;
    out[i] = (uint64_t)t;
    borrow = (uint64_t)(t >> 64) & 1;
  }
  return borrow;
}

static void reduce512_one(const uint8_t in[64], uint8_t out[32]) {
  uint64_t x[8], q2[10], ql[10], r[5], s[5];
  for (int i = 0; i < 8; i++) {
    x[i] = 0;
    for (int b = 0; b < 8; b++) x[i] |= (uint64_t)in[i * 8 + b] << (8 * b);
  }
  mul_limbs(x + 3, 5, MU_LIMBS, 5, q2);  /* floor(x / b^3) * mu */
  mul_limbs(q2 + 5, 5, L_LIMBS, 5, ql);  /* q * L, q = q2 / b^5 */
  sub5(x, ql, r);                        /* 0 <= x - q*L < 3L < 2^320 */
  while (!sub5(r, L_LIMBS, s)) memcpy(r, s, sizeof(r));
  for (int i = 0; i < 4; i++)
    for (int b = 0; b < 8; b++) out[i * 8 + b] = (uint8_t)(r[i] >> (8 * b));
}

/* n 64-byte little-endian values -> n 32-byte little-endian residues. */
void reduce512_mod_l(const uint8_t *in, int64_t n, uint8_t *out) {
  for (int64_t i = 0; i < n; i++)
    reduce512_one(in + (uint64_t)i * 64, out + (uint64_t)i * 32);
}

/* The challenge scalars of n messages of the form prefix_i || msg_i,
 * where every prefix is a fixed 64 bytes (the verifier's R || A) laid
 * out contiguously: saves the host from materializing n concatenated
 * byte strings. Each digest is reduced mod L where it was made: out
 * holds n * 32 bytes, SHA-512(prefix_i || msg_i) mod L. */
void sha512_batch_prefixed_mod_l(const uint8_t *prefix, const uint8_t *buf,
                                 const uint64_t *offsets, int64_t n,
                                 uint8_t *out) {
#ifdef _OPENMP
#pragma omp parallel for schedule(static) if (n >= PARALLEL_MIN_BATCH)
#endif
  for (int64_t i = 0; i < n; i++) {
    sha512_ctx c;
    uint8_t digest[64];
    sha512_init(&c);
    sha512_update(&c, prefix + (uint64_t)i * 64, 64);
    sha512_update(&c, buf + offsets[i], offsets[i + 1] - offsets[i]);
    sha512_final(&c, digest);
    reduce512_one(digest, out + (uint64_t)i * 32);
  }
}
